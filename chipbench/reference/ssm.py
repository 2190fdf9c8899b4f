"""Mamba2 (arXiv:2405.21060) language model loss, plain float32.

The SSD mixer is the paper's minimal chunked form (``ssd_minimal_discrete``
of the authors' code): within a chunk the quadratic form with the
segment-sum decay matrix, across chunks a recurrence over chunk states.
The projections are the published fused ``in_proj`` split into its
z | x | B | C | dt parts, each depthwise causal conv is the published
``conv1d`` (width ``d_conv``, padding ``d_conv - 1``), the gated RMSNorm
normalises ``y * silu(z)``, and the head is tied to the embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import cross_entropy_sum, einsum, rmsnorm
from chipbench.weights import ssm_sizes


def segsum(x):
    """(..., T) -> (..., T, T): sum of x[j+1..i] at [i, j] for j <= i,
    -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], (*x.shape, T))        # [.., i, j] = x[i]
    low = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(low, xx, 0.0)
    ss = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), ss, -jnp.inf)


def ssd(X, A, B, C, chunk: int, mode: str):
    """X (b,T,h,p) = x*dt; A (b,T,h) = A*dt; B, C (b,T,h,n) -> Y (b,T,h,p)."""
    b, T, h, p = X.shape
    c = T // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, h, -1)
    C = C.reshape(b, c, chunk, h, -1)
    A = jnp.moveaxis(A.reshape(b, c, chunk, h), -1, 1)        # (b,h,c,l)
    A_cum = jnp.cumsum(A, axis=-1)
    Lm = jnp.exp(segsum(A))                                    # (b,h,c,l,l)
    G = einsum("bclhn,bcshn->bclsh", C, B, mode=mode)
    M = G * jnp.transpose(Lm, (0, 2, 3, 4, 1))                 # (b,c,l,s,h)
    Y_diag = einsum("bclsh,bcshp->bclhp", M, X, mode=mode)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                   # (b,h,c,l)
    Bd = B * jnp.transpose(decay, (0, 2, 3, 1))[..., None]
    states = einsum("bclhn,bclhp->bchpn", Bd, X, mode=mode)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                    mode=mode)[:, :-1]
    Cd = C * jnp.transpose(jnp.exp(A_cum), (0, 2, 3, 1))[..., None]
    Y_off = einsum("bclhn,bchpn->bclhp", Cd, states, mode=mode)
    return (Y_diag + Y_off).reshape(b, T, h, p)


def conv(x, w, bias):
    """Depthwise causal conv: out[t] = sum_k w[k] x[t + k - (W-1)] + bias."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    T = x.shape[1]
    return sum(xp[:, k:k + T] * w[k].astype(jnp.float32)
               for k in range(W)) + bias.astype(jnp.float32)


def layer(lp, h, c: dict, mode: str):
    z = ssm_sizes(c)
    b, T, _ = h.shape
    eps = c["rms_norm_eps"]
    f = lambda a: a.astype(jnp.float32)
    m = lp["mamba"]
    x = rmsnorm(h, lp["ln1"]["scale"], eps)
    proj = lambda w: einsum("btd,de->bte", x, f(w), mode=mode)
    gate, xs, Bp, Cp, dt = (proj(m[k]) for k in
                            ("w_z", "w_x", "w_B", "w_C", "w_dt"))
    xs = jax.nn.silu(conv(xs, m["conv_x_w"], m["conv_x_b"]))
    Bp = jax.nn.silu(conv(Bp, m["conv_B_w"], m["conv_B_b"]))
    Cp = jax.nn.silu(conv(Cp, m["conv_C_w"], m["conv_C_b"]))
    dt = jax.nn.softplus(dt + f(m["dt_bias"]))                 # (b,T,H)
    A = -jnp.exp(f(m["A_log"]))
    H, P, G, S = z["H"], z["P"], z["G"], z["S"]
    xs = xs.reshape(b, T, H, P)
    rep = H // G
    Bh = jnp.repeat(Bp.reshape(b, T, G, S), rep, axis=2)
    Ch = jnp.repeat(Cp.reshape(b, T, G, S), rep, axis=2)
    y = ssd(xs * dt[..., None], A * dt, Bh, Ch, z["Q"], mode)
    y = y + xs * f(m["D"])[:, None]
    y = y.reshape(b, T, H * P) * jax.nn.silu(gate)
    y = rmsnorm(y, m["norm"]["scale"], eps)
    return h + einsum("bte,ed->btd", y, f(m["out_proj"]), mode=mode)


def loss(params, tokens, labels, c: dict, mode: str = "f32",
         head_chunk: int = 512):
    """Mean next-token cross entropy over ``tokens`` (b, T)."""
    emb = params["embed"]["tok"].astype(jnp.float32)
    h = jnp.take(emb, tokens, axis=0)

    @jax.checkpoint
    def body(h, lp):
        return layer(lp, h, c, mode), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = rmsnorm(h, params["final_norm"]["scale"], c["rms_norm_eps"])
    b, T, d = h.shape
    head_chunk = min(head_chunk, T)
    n = T // head_chunk

    @jax.checkpoint
    def head(carry, xs):
        hh, ll = xs                                            # (b,k,d), (b,k)
        logits = einsum("bkd,vd->bkv", hh, emb, mode=mode)
        return carry + cross_entropy_sum(logits.reshape(-1, logits.shape[-1]),
                                         ll.reshape(-1)), None

    hs = jnp.moveaxis(h.reshape(b, n, head_chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, n, head_chunk), 1, 0)
    total, _ = jax.lax.scan(head, jnp.zeros((), jnp.float32), (hs, ls))
    return total / (b * T)
