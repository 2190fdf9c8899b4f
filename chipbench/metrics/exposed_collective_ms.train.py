"""The part of ``collective_ms.train`` in which no other op runs on that
chip, per step, averaged over the chips."""
from chipbench import trace


def read(rec):
    if rec["kind"] != "train" or "trace" not in rec or not rec["steps"]:
        return None
    coll, exposed = trace.collective_s(rec["trace"])
    if coll == 0.0:
        return None
    return 1e3 * exposed / rec["steps"]
