"""The prompt positions the engine prefilled past each prompt's true
length (its bucket's padding; ``ContinuousBatcher.pad_positions``) over
the true prompt positions, for the requests admitted in the window, in
%."""


def read(rec):
    if rec["kind"] != "serve" or "pad_positions" not in rec \
            or not rec["prefill_lens"]:
        return None
    return 100.0 * rec["pad_positions"] / sum(rec["prefill_lens"])
