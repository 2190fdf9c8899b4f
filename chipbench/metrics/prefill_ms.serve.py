"""Device time per prefill call: the ops of the prefill program
(``jit__prefill``) in the traced window, over the prefills made in it."""
from chipbench import trace


def read(rec):
    if rec["kind"] != "serve" or "trace" not in rec \
            or not rec["prefill_lens"]:
        return None
    s = trace.module_s(rec["trace"], r"prefill")
    if s == 0.0:
        return None
    return 1e3 * s / len(rec["prefill_lens"])
