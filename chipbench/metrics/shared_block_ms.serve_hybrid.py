"""The decode's leaf device time per call in the hybrid's shared blocks:
the ops whose innermost model scope is ``attn``, ``kv_write`` (the cache
write) or ``mlp`` (with the application's adapter and projection), in ms
(``kinds/serve_hybrid.py:decode_scope_ms``)."""


def read(rec):
    split = rec.get("decode_scope_ms")
    if rec["kind"] != "serve" or split is None:
        return None
    return split["attn"] + split["kv_write"] + split["mlp"]
