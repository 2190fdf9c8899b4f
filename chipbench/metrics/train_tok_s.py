"""Global tokens of every step the window completed, over the window."""


def read(rec):
    if rec["kind"] != "train":
        return None
    return rec["tokens"] / rec["window_s"]
