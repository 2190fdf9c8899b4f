"""Per engine iteration (the benchmark's span ``chipbench.iteration``
around ``admit`` and ``step_decode``): its wall time minus the device's
busy time inside it, in ms."""
from chipbench import trace


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec:
        return None
    red = rec["trace"]
    its = trace.spans_named(red, "chipbench.iteration")
    if not its:
        return None
    wall = trace.total(trace.union(its)) * 1e-9
    return 1e3 * (wall - trace.device_s_within(red, its)) / len(its)
