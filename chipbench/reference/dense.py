"""Llama-style dense decoder (h2o-danube3, arXiv:2407.09276) logits,
plain float32: RMSNorm, grouped-query attention with rotary embeddings
(halves rotated, as in the published Llama code) over a causal sliding
window, SwiGLU MLP, an untied or tied head.  One whole sequence at a
time, no cache: the logits at every position."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import einsum, rmsnorm
from chipbench.weights import dense_sizes


def rope(x, theta: float):
    """x (T, heads, hd): rotate the two halves of each head by position."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs    # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(lp, h, c: dict, mode: str):
    z = dense_sizes(c)
    H, K, hd = z["H"], z["K"], z["hd"]
    T = h.shape[0]
    eps = c["rms_norm_eps"]
    f = lambda a: a.astype(jnp.float32)
    a = lp["attn"]
    x = rmsnorm(h, lp["ln1"]["scale"], eps)
    q = einsum("td,de->te", x, f(a["wq"]), mode=mode).reshape(T, H, hd)
    k = einsum("td,de->te", x, f(a["wk"]), mode=mode).reshape(T, K, hd)
    v = einsum("td,de->te", x, f(a["wv"]), mode=mode).reshape(T, K, hd)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    g = H // K
    q = q.reshape(T, K, g, hd) / jnp.sqrt(jnp.float32(hd))
    s = einsum("tkgd,skd->kgts", q, k, mode=mode)
    i = jnp.arange(T)
    ok = i[None, :] <= i[:, None]
    if c.get("sliding_window"):
        ok &= i[None, :] > i[:, None] - c["sliding_window"]
    s = jnp.where(ok, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("kgts,skd->tkgd", p, v, mode=mode).reshape(T, H * hd)
    h = h + einsum("te,ed->td", o, f(a["wo"]), mode=mode)
    m = lp["mlp"]
    x = rmsnorm(h, lp["ln2"]["scale"], eps)
    up = einsum("td,df->tf", x, f(m["w_up"]), mode=mode)
    gate = einsum("td,df->tf", x, f(m["w_gate"]), mode=mode)
    return h + einsum("tf,fd->td", jax.nn.silu(gate) * up, f(m["w_down"]),
                      mode=mode)


def logits(params, tokens, c: dict, mode: str = "f32"):
    """(T,) tokens -> (T, V) float32 logits."""
    h = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)

    def body(h, lp):
        return layer(lp, h, c, mode), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = rmsnorm(h, params["final_norm"]["scale"], c["rms_norm_eps"])
    head = params["embed"].get("head")
    if head is None:
        return einsum("td,vd->tv", h, params["embed"]["tok"].astype(
            jnp.float32), mode=mode)
    return einsum("td,dv->tv", h, head.astype(jnp.float32), mode=mode)
