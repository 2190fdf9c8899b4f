"""The decode's leaf device time per call in the Mamba2 mixers: the ops
whose innermost model scope is ``mamba`` or ``ssd`` (the state step
inside it), in ms (``kinds/serve_hybrid.py:decode_scope_ms``)."""


def read(rec):
    split = rec.get("decode_scope_ms")
    if rec["kind"] != "serve" or split is None:
        return None
    return split["mamba"] + split["ssd"]
