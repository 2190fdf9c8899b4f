"""The hybrid decode program's share of its roofline: the least time of
every decode call in the traced window (operations at peak or bytes at
the memory's bandwidth, whichever is longer, for the active slots at
their actual lengths: ``shapes_hybrid.decode_flops`` /
``shapes_hybrid.decode_bytes``) over the device time of the decode
program (``jit__decode``)."""
from chipbench import shapes, shapes_hybrid, trace


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec \
            or rec["config"]["family"] != "hybrid" \
            or not rec["decode_lens"]:
        return None
    dev = trace.module_s(rec["trace"], r"decode")
    if dev == 0.0:
        return None
    c, peak = rec["config"], rec["peak"]
    least = sum(shapes.least_seconds(shapes_hybrid.decode_flops(c, lens),
                                     shapes_hybrid.decode_bytes(c, lens),
                                     peak)
                for lens in rec["decode_lens"])
    return 100.0 * least / dev
