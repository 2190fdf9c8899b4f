"""The decode program's share of its roofline: the least time of every
decode call in the traced window (operations at peak or bytes at the
memory's bandwidth, whichever is longer, for the active slots at their
actual lengths: ``shapes.decode_flops`` / ``shapes.decode_bytes``) over
the device time of the decode program (``jit__decode``)."""
from chipbench import shapes, trace


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec \
            or not rec["decode_lens"]:
        return None
    dev = trace.module_s(rec["trace"], r"decode")
    if dev == 0.0:
        return None
    c, peak = rec["config"], rec["peak"]
    least = sum(shapes.least_seconds(shapes.decode_flops(c, lens),
                                     shapes.decode_bytes(c, lens), peak)
                for lens in rec["decode_lens"])
    return 100.0 * least / dev
