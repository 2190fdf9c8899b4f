"""Model operations of the steps in the traced window (forward and
backward, no recomputation, ``shapes.train_flops_per_token``) over window
x chips x the chip's bf16 peak."""


def read(rec):
    if rec["kind"] != "train" or "trace" not in rec:
        return None
    red = rec["trace"]
    flops = rec["tokens"] * rec["flops_per_token"]
    return 100.0 * flops / (red.window_s * rec["chips"]
                            * rec["peak"]["bf16_flops_per_s"])
