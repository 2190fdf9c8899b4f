"""Zamba2 (arXiv:2411.15242; transformers' ``modeling_zamba2``) logits,
plain float32, and the seeded weights the program and this reference share.

Every layer is a Mamba2 layer ``h + Mamba2(RMSNorm(h + t))``.  At the
layers of ``hybrid_layer_ids`` ``t`` is what one of ``num_mem_blocks``
shared blocks (A, B, A, ... in turn) gives at that application, through
the application's own projection; elsewhere ``t`` is 0.  The shared block
at application ``j``, with ``x0`` the token embeddings:

    u = RMSNorm(concat(h, x0))                      (2·hidden wide)
    o = Wo · attention(u)     causal MHA, RoPE over the whole head,
                              scores scaled by (head_dim / 2) ** -0.5
    a = RMSNorm(o)
    [g, v] = W_gu a + B_j (A_j a)                   (rank adapter_rank)
    t = Linear_j(W_down (gelu(g) * v))              (exact GELU)

The Mamba2 mixer is ``reference/ssm.py``'s (its chunked ``ssd`` and causal
``conv``), with the gated RMSNorm normalised over each of ``mamba_ngroups``
channel groups.  The head is tied to the embedding.  One whole sequence,
no cache; nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference.dense import rope
from chipbench.reference.numerics import einsum, rmsnorm
from chipbench.reference.ssm import conv, ssd


def sizes(c: dict) -> dict:
    d = c["hidden_size"]
    return {"L": c["num_hidden_layers"], "d": d,
            "da": c["attention_hidden_size"],
            "H": c["num_attention_heads"], "K": c["num_key_value_heads"],
            "hd": c["attention_head_dim"], "f": c["intermediate_size"],
            "r": c["adapter_rank"], "nb": c["num_mem_blocks"],
            "ids": tuple(c["hybrid_layer_ids"]), "V": c["vocab_size"],
            "di": c["mamba_expand"] * d, "S": c["mamba_d_state"],
            "G": c["mamba_ngroups"], "W": c["mamba_d_conv"],
            "Hm": c["n_mamba_heads"], "P": c["mamba_headdim"],
            "Q": c["chunk_size"]}


def _mamba_config(c: dict) -> dict:
    """The Mamba2 sizes under ``weights.ssm_weights``' names."""
    return {"n_layer": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "expand": c["mamba_expand"], "d_state": c["mamba_d_state"],
            "ngroups": c["mamba_ngroups"], "d_conv": c["mamba_d_conv"],
            "headdim": c["mamba_headdim"], "vocab_size": c["vocab_size"],
            "chunk_size": c["chunk_size"], "dtype": c["dtype"]}


def weights(c: dict, key) -> dict:
    """The program's tree (``embed``, ``final_norm``, the Mamba layer stack
    ``blocks``, and ``shared_attn``: the shared blocks ``mem`` and, per
    application, its adapter and projection ``apps``).  The Mamba layers
    are drawn as ``weights.ssm_weights`` draws them (mamba_ssm's
    initialisation); every matrix of the shared blocks, adapters and
    projections N(0, 0.02) (Zamba2Config's ``initializer_range``); norms
    1."""
    z = sizes(c)
    d, da, H, K, hd, f, r = (z[k] for k in "d da H K hd f r".split())
    dt = jnp.dtype(c["dtype"])
    params = W.ssm_weights(_mamba_config(c), key)
    n = lambda name, shape: W._normal(key, name, shape, dt, 0.02)
    ones = lambda m: {"scale": jnp.ones((m,), dt)}
    mem = [{"ln_in": ones(da),
            "attn": {"wq": n(f"mem{b}.wq", (da, H * hd)),
                     "wk": n(f"mem{b}.wk", (da, K * hd)),
                     "wv": n(f"mem{b}.wv", (da, K * hd)),
                     "wo": n(f"mem{b}.wo", (H * hd, d))},
            "ln_ff": ones(d),
            "mlp": {"w_gate_up": n(f"mem{b}.w_gate_up", (d, 2 * f)),
                    "w_down": n(f"mem{b}.w_down", (f, d))}}
           for b in range(z["nb"])]
    apps = [{"adapter_in": n(f"app{j}.adapter_in", (d, r)),
             "adapter_out": n(f"app{j}.adapter_out", (r, 2 * f)),
             "linear": n(f"app{j}.linear", (d, d))}
            for j in range(len(z["ids"]))]
    params["shared_attn"] = {"mem": mem, "apps": apps}
    return params


def shared_block(blk, app, h, x0, c: dict, mode: str):
    """(T, d) -> (T, d): the shared block at one application, through its
    adapter and projection (the module doc's ``t``)."""
    z = sizes(c)
    H, K, hd = z["H"], z["K"], z["hd"]
    T = h.shape[0]
    eps = c["rms_norm_eps"]
    f = lambda a: a.astype(jnp.float32)
    a = blk["attn"]
    u = rmsnorm(jnp.concatenate([h, x0], axis=-1), blk["ln_in"]["scale"],
                eps)
    q = einsum("td,de->te", u, f(a["wq"]), mode=mode).reshape(T, H, hd)
    k = einsum("td,de->te", u, f(a["wk"]), mode=mode).reshape(T, K, hd)
    v = einsum("td,de->te", u, f(a["wv"]), mode=mode).reshape(T, K, hd)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    g = H // K
    q = q.reshape(T, K, g, hd) * (hd / 2) ** -0.5
    s = einsum("tkgd,skd->kgts", q, k, mode=mode)
    i = jnp.arange(T)
    s = jnp.where(i[None, :] <= i[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("kgts,skd->tkgd", p, v, mode=mode).reshape(T, H * hd)
    o = einsum("te,ed->td", o, f(a["wo"]), mode=mode)
    x = rmsnorm(o, blk["ln_ff"]["scale"], eps)
    m = blk["mlp"]
    gu = einsum("td,df->tf", x, f(m["w_gate_up"]), mode=mode) + einsum(
        "tr,rf->tf", einsum("td,dr->tr", x, f(app["adapter_in"]), mode=mode),
        f(app["adapter_out"]), mode=mode)
    gate, up = jnp.split(gu, 2, axis=-1)
    y = jax.nn.gelu(gate, approximate=False) * up
    y = einsum("tf,fd->td", y, f(m["w_down"]), mode=mode)
    return einsum("td,de->te", y, f(app["linear"]), mode=mode)


def mamba_layer(lp, h, t, c: dict, mode: str):
    """(T, d) -> (T, d): ``h + Mamba2(RMSNorm(h + t))``; T a multiple of
    the chunk."""
    z = sizes(c)
    H, P, G, S = z["Hm"], z["P"], z["G"], z["S"]
    T = h.shape[0]
    eps = c["rms_norm_eps"]
    f = lambda a: a.astype(jnp.float32)
    m = lp["mamba"]
    x = rmsnorm(h + t, lp["ln1"]["scale"], eps)[None]
    proj = lambda w: einsum("btd,de->bte", x, f(w), mode=mode)
    gate, xs, Bp, Cp, dt = (proj(m[k]) for k in
                            ("w_z", "w_x", "w_B", "w_C", "w_dt"))
    xs = jax.nn.silu(conv(xs, m["conv_x_w"], m["conv_x_b"]))
    Bp = jax.nn.silu(conv(Bp, m["conv_B_w"], m["conv_B_b"]))
    Cp = jax.nn.silu(conv(Cp, m["conv_C_w"], m["conv_C_b"]))
    dt = jax.nn.softplus(dt + f(m["dt_bias"]))                 # (1,T,H)
    A = -jnp.exp(f(m["A_log"]))
    xs = xs.reshape(1, T, H, P)
    rep = H // G
    Bh = jnp.repeat(Bp.reshape(1, T, G, S), rep, axis=2)
    Ch = jnp.repeat(Cp.reshape(1, T, G, S), rep, axis=2)
    y = ssd(xs * dt[..., None], A * dt, Bh, Ch, z["Q"], mode)
    y = y + xs * f(m["D"])[:, None]
    y = (y.reshape(T, H * P) * jax.nn.silu(gate[0])).reshape(T, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(T, H * P) * f(m["norm"]["scale"])
    return h + einsum("te,ed->td", y, f(m["out_proj"]), mode=mode)


def logits(params, tokens, c: dict, mode: str = "f32"):
    """(T,) tokens -> (T, V) float32 logits."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, tokens, c, mode)


def _logits(params, tokens, c: dict, mode: str):
    z = sizes(c)
    T = tokens.shape[0]
    # the SSD works in whole chunks; padding at the end changes no
    # earlier position of a causal model
    tokens = jnp.pad(tokens, (0, -T % z["Q"]))
    x0 = jnp.take(params["embed"]["tok"], tokens,
                  axis=0).astype(jnp.float32)
    zero = jnp.zeros_like(x0)
    blocks, shared = params["blocks"], params["shared_attn"]

    def run(h, lo, hi):
        """Mamba-only layers lo..hi-1."""
        if hi <= lo:
            return h
        lps = jax.tree.map(lambda a: a[lo:hi], blocks)
        h, _ = jax.lax.scan(
            lambda h, lp: (mamba_layer(lp, h, zero, c, mode), None), h, lps)
        return h

    h, lo = x0, 0
    for j, i in enumerate(z["ids"]):
        h = run(h, lo, i)
        t = shared_block(shared["mem"][j % z["nb"]], shared["apps"][j], h,
                         x0, c, mode)
        h = mamba_layer(jax.tree.map(lambda a: a[i], blocks), h, t, c, mode)
        lo = i + 1
    h = run(h, lo, z["L"])
    h = rmsnorm(h, params["final_norm"]["scale"], c["rms_norm_eps"])
    out = einsum("td,vd->tv", h, params["embed"]["tok"].astype(jnp.float32),
                 mode=mode)
    return out[:T]
