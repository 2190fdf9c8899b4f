"""95th percentile (nearest rank) of every gap between consecutive output
tokens of a request, the later token produced in the window."""
import math


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = sorted(rec["itl_s"])
    if not v:
        return None
    return 1e3 * v[max(math.ceil(0.95 * len(v)) - 1, 0)]
