"""Shared building blocks: norms, rotary embeddings, MLPs, embeddings.

Pure-function style: ``init_*`` returns a params dict, ``apply`` functions
are stateless.  Everything is jnp-only so it works under ``jax.eval_shape``
(the dry-run path never allocates real parameters).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# activation-sharding hints (set by the launch layer, honored model-wide):
# batch axes pin the leading dim; d_axis optionally shards a trailing
# feature dim between layers (Megatron-SP-along-d — shrinks saved-for-
# backward stacks by the TP degree).
# ---------------------------------------------------------------------------
_ACT_BATCH: contextvars.ContextVar = contextvars.ContextVar(
    "act_batch_axes", default=None)
_ACT_DMODEL: contextvars.ContextVar = contextvars.ContextVar(
    "act_d_axis", default=None)
_ACT_KV: contextvars.ContextVar = contextvars.ContextVar(
    "act_kv_spec", default=None)      # (batch_entry, seq_entry) for caches


@contextlib.contextmanager
def activation_batch_axes(axes, d_axis=None, kv=None):
    tok = _ACT_BATCH.set(tuple(axes) if axes else None)
    tok2 = _ACT_DMODEL.set(d_axis)
    tok3 = _ACT_KV.set(kv)
    try:
        yield
    finally:
        _ACT_BATCH.reset(tok)
        _ACT_DMODEL.reset(tok2)
        _ACT_KV.reset(tok3)


def pin_kv(arr):
    """Pin a (B, S, K, hd) cache-shaped tensor to the serve-cache layout.

    The one-hot cache update and the prefill DUS otherwise produce full-
    cache-sized intermediates sharded on batch only — 16× the per-chip
    bytes of the (batch × seq-over-model) cache layout (verified: qwen
    prefill_32k at 55 GB temp without this pin)."""
    spec = _ACT_KV.get()
    if spec is None or arr is None:
        return arr
    b, s = spec
    return jax.lax.with_sharding_constraint(
        arr, PartitionSpec(b, s, *([None] * (arr.ndim - 2))))


def pin_act(x, *, shard_last: bool = False):
    """Constrain x to (batch_axes, None…, [d_axis]) if hints are active."""
    axes = _ACT_BATCH.get()
    if axes is None or x is None:
        return x
    d_axis = _ACT_DMODEL.get() if shard_last else None
    if x.ndim == 1:
        spec = PartitionSpec(axes)
    else:
        spec = PartitionSpec(axes, *([None] * (x.ndim - 2)), d_axis)
    return jax.lax.with_sharding_constraint(x, spec)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.truncated_normal(key, -2, 2, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict, x, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(p: dict, x, eps: float):
    return layernorm(p, x, eps) if "bias" in p else rmsnorm(p, x, eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """positions: (...,) int32 → (..., head_dim/2) angles, fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x, positions, theta: float):
    """x: (B, T, H, D) with D even; positions: (B, T) or (T,)."""
    d = x.shape[-1]
    ang = rope_angles(positions, d, theta)           # (B?, T, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:                          # broadcast over heads
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU or plain)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f, dt = cfg.d_model, cfg.d_ff, _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, f), dt),
         "w_down": dense_init(ks[1], (f, d), dt)}
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[2], (d, f), dt)
    return p


def _act(name: str):
    """``gelu`` is the tanh form; ``gelu_exact`` the erf one."""
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_exact": functools.partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu}[name]


def mlp(p: dict, x, cfg: ModelConfig):
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = _act(cfg.act)(x @ p["w_gate"]) * h
    else:
        h = _act(cfg.act)(h)
    return h @ p["w_down"]


def _allgather_last(comm, x, strategy=None):
    """All-gather the LAST axis over ``comm`` in global-rank order.

    The §3 mock-ups concatenate along the leading dim, so the feature
    axis is moved to the front for the wire and moved back after —
    global rank == model rank on a model-only topology, so the
    concatenation order matches the column-slice order exactly."""
    t = jnp.moveaxis(x, -1, 0)
    g = comm.allgather(t, strategy=strategy)
    return jnp.moveaxis(g, 0, -1)


def _tp_cols(comm, w, width: int, axis: int = 1):
    """This model rank's ``width``-column block of ``w`` along ``axis``."""
    r = comm.topo.global_rank()
    return jax.lax.dynamic_slice_in_dim(w, r * width, width, axis=axis)


def mlp_tp(p: dict, x, cfg: ModelConfig, *, comm, strategy=None):
    """Tensor-parallel MLP, bit-identical to :func:`mlp` — forward AND
    per-rank gradients.

    ALL matmuls are column-parallel: each model rank computes its f/tp
    (then d/tp) output columns and an allgather over the model axis
    reassembles the full activation — pure concatenation, so every
    element is produced by exactly the same dot products as the
    replicated path (the bit-identity the TP==replicated pin asserts).

    The backward is a custom VJP (see :func:`_mlp_tp_bwd`) rather than
    plain AD: transposing the forward allgathers would hand each rank a
    tp-scaled PARTIAL cotangent (every rank's replicated loss copy
    contributes through the collective transpose), which poisons every
    upstream gradient's bit-identity.  The custom rule instead computes
    column blocks of exactly the replicated backward's einsums and
    allgathers the input cotangent full, so non-MLP grads stay bitwise
    replicated over the model axis and the zero-padded MLP weight-grad
    blocks assemble EXACTLY under one model-axis psum (adding zeros is
    exact).
    """
    tp = comm.topo.p()
    f, d = cfg.d_ff, cfg.d_model
    if f % tp or d % tp:
        raise ValueError(
            f"tensor-parallel degree {tp} must divide d_ff={f} and "
            f"d_model={d}")
    return _mlp_tp(cfg.act, comm, strategy, x, p["w_up"],
                   p.get("w_gate"), p["w_down"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _mlp_tp(act, comm, strategy, x, w_up, w_gate, w_down):
    y, _ = _mlp_tp_parts(act, comm, strategy, x, w_up, w_gate, w_down)
    return y


def _mlp_tp_parts(act, comm, strategy, x, w_up, w_gate, w_down):
    tp = comm.topo.p()
    f, d = w_up.shape[1], w_down.shape[1]
    h = x @ _tp_cols(comm, w_up, f // tp)
    if w_gate is not None:
        h = _act(act)(x @ _tp_cols(comm, w_gate, f // tp)) * h
    else:
        h = _act(act)(h)
    a = _allgather_last(comm, h, strategy)               # (.., f) full
    y = a @ _tp_cols(comm, w_down, d // tp)
    return _allgather_last(comm, y, strategy), a         # (.., d) full


def _mlp_tp_fwd(act, comm, strategy, x, w_up, w_gate, w_down):
    y, a = _mlp_tp_parts(act, comm, strategy, x, w_up, w_gate, w_down)
    return y, (x, a, w_up, w_gate, w_down)


def _mlp_tp_bwd(act, comm, strategy, res, dy):
    """Column blocks of the replicated backward, assembled by gathers.

    Every einsum below is a contiguous output slice of the corresponding
    replicated-AD einsum with identical contraction dims, so each block
    is bitwise equal to its slice of the replicated gradient; the input
    cotangent dx is allgathered back to full so everything upstream of
    the MLP sees exactly the replicated cotangent.
    """
    x, a, w_up, w_gate, w_down = res
    tp = comm.topo.p()
    f, d = w_up.shape[1], w_down.shape[1]
    fl, dl = f // tp, d // tp
    r = comm.topo.global_rank()

    # dh slice: replicated dh = dy @ w_down.T; rows fl of w_down give cols
    dh_loc = dy @ jax.lax.dynamic_slice_in_dim(w_down, r * fl, fl, 0).T
    h1_loc = x @ _tp_cols(comm, w_up, fl)
    if w_gate is None:
        _, evjp = jax.vjp(_act(act), h1_loc)
        (dh1_loc,) = evjp(dh_loc)
        dhg_loc = None
    else:
        hg_loc = x @ _tp_cols(comm, w_gate, fl)
        _, evjp = jax.vjp(lambda u, g: _act(act)(g) * u, h1_loc, hg_loc)
        dh1_loc, dhg_loc = evjp(dh_loc)

    bt = x.reshape(-1, x.shape[-1])                      # (B·T, d)
    def _wgrad(u, v, shape, col, width):
        blk = u.T @ v.reshape(-1, v.shape[-1])           # (in, width)
        return jax.lax.dynamic_update_slice(
            jnp.zeros(shape, blk.dtype), blk, (0, col))

    dw_up = _wgrad(bt, dh1_loc, (x.shape[-1], f), r * fl, fl)
    dw_gate = None if w_gate is None else \
        _wgrad(bt, dhg_loc, (x.shape[-1], f), r * fl, fl)
    dy_loc = jax.lax.dynamic_slice_in_dim(dy, r * dl, dl, dy.ndim - 1)
    dw_down = _wgrad(a.reshape(-1, f), dy_loc, (f, d), r * dl, dl)

    # full f-cotangents (exact concatenation of exact slices), then the
    # d-column block of dx and a final gather back to full
    dh1 = _allgather_last(comm, dh1_loc, strategy)
    up_rows = jax.lax.dynamic_slice_in_dim(w_up, r * dl, dl, 0)
    dx_loc = dh1 @ up_rows.T
    if w_gate is not None:
        dhg = _allgather_last(comm, dhg_loc, strategy)
        gate_rows = jax.lax.dynamic_slice_in_dim(w_gate, r * dl, dl, 0)
        dx_loc = dx_loc + dhg @ gate_rows.T
    dx = _allgather_last(comm, dx_loc, strategy)
    return dx, dw_up, dw_gate, dw_down


_mlp_tp.defvjp(_mlp_tp_fwd, _mlp_tp_bwd)


def mlp_tp_reduce(p: dict, x, cfg: ModelConfig, *, comm, strategy=None):
    """Megatron-style TP MLP: column-parallel up/gate, ROW-parallel down,
    one allreduce over the model axis on the output.

    Halves the activation traffic of :func:`mlp_tp` (no intermediate
    f-gather) but sums partial products across ranks, so it is equal to
    :func:`mlp` only to rounding — pinned allclose, never bit-identical.
    """
    tp = comm.topo.p()
    f = cfg.d_ff
    if f % tp:
        raise ValueError(
            f"tensor-parallel degree {tp} must divide d_ff={f}")
    fl = f // tp
    h = x @ _tp_cols(comm, p["w_up"], fl)
    if "w_gate" in p:
        h = _act(cfg.act)(x @ _tp_cols(comm, p["w_gate"], fl)) * h
    else:
        h = _act(cfg.act)(h)
    r = comm.topo.global_rank()
    down = jax.lax.dynamic_slice_in_dim(p["w_down"], r * fl, fl, axis=0)
    return comm.allreduce(h @ down, strategy=strategy)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": dense_init(k1, (cfg.vocab_size, cfg.d_model), dt, scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dt)
    return p


def embed(p: dict, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: dict, x):
    if "head" in p:
        return x @ p["head"]
    return x @ p["tok"].T
