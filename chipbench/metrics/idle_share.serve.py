"""Share of the traced window in which no op ran on the chip:
1 - (union of op intervals) / window."""
from chipbench import trace


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec:
        return None
    return 100.0 * trace.idle_share(rec["trace"])
