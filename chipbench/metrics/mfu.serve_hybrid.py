"""Model operations of the prompts prefilled and the tokens decoded in the
traced window of a hybrid cell (``shapes_hybrid.prefill_flops`` /
``shapes_hybrid.decode_flops``, active slots only) over window x the chip's
bf16 peak."""
from chipbench import shapes_hybrid


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec \
            or rec["config"]["family"] != "hybrid":
        return None
    c = rec["config"]
    flops = sum(shapes_hybrid.prefill_flops(c, n)
                for n in rec["prefill_lens"])
    flops += sum(shapes_hybrid.decode_flops(c, lens)
                 for lens in rec["decode_lens"])
    return 100.0 * flops / (rec["trace"].window_s
                            * rec["peak"]["bf16_flops_per_s"])
