"""95th percentile (nearest rank) of the time from when each request due
in the window was due to its first token; a request that failed or never
got one counts as missing every limit (infinite).  Per layer: at the
cell's rate it swings with the bursts that fill every slot, too widely
for a bound."""
import math


def read(rec):
    if rec["kind"] != "serve":
        return None
    v = sorted(rec["ttft_s"])
    if not v:
        return None
    return 1e3 * v[max(math.ceil(0.95 * len(v)) - 1, 0)]
