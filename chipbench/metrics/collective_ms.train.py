"""Device time of the collective ops per step (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute; an async start-done pair from
start to done), the union per chip, averaged over the chips."""
from chipbench import trace


def read(rec):
    if rec["kind"] != "train" or "trace" not in rec or not rec["steps"]:
        return None
    coll, _ = trace.collective_s(rec["trace"])
    if coll == 0.0:
        return None
    return 1e3 * coll / rec["steps"]
