"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

Grid: (batch, head_block, chunk) with the chunk dimension sequential
("arbitrary") — the (heads_blk, P, S) recurrent state lives in VMEM scratch
across chunks, so HBM sees each x/B/C element exactly once (the kernel is
bandwidth-optimal; the lax reference rematerializes inter-chunk states
through HBM).  Within a chunk the intra-chunk quadratic term runs on the
MXU per head with (Q × Q) tiles.

Layout: head-major (B, H, T, P) / (B, T, S) with Q (chunk length) a
multiple of 8 sublanes and P, S multiples of 128 lanes where possible.

Validated with interpret=True against kernels/ref.py::ssd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_s, *,
            nheads_blk: int, chunk: int, nchunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_s[...] = jnp.zeros_like(state_s)

    x = x_ref[0].astype(jnp.float32)          # (hb, Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (hb, Q)
    A = a_ref[:, 0].astype(jnp.float32)       # (hb,)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, S)   (group-shared)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, S)

    da = dt * A[:, None]                      # (hb, Q), ≤ 0
    # within-chunk decay: an inclusive prefix sum over Q, as a matmul with
    # the upper-triangular ones matrix (the TPU lowering has no cumsum)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    upper = (qi <= ti).astype(jnp.float32)                         # [t, q]
    cum = jax.lax.dot_general(da, upper, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (hb,Q)
    seg_end = cum[:, chunk - 1:]              # (hb, 1), a static slice

    # intra-chunk: scores[h,q,t] = (C[q]·B[t]) e^{cum_q - cum_t} dt_t (q≥t)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    diff = cum[:, :, None] - cum[:, None, :]                       # (hb,Q,Q)
    L = jnp.where((qi >= ti)[None], jnp.exp(diff), 0.0)            # (hb,Q,Q)
    scores = cb[None] * L * dt[:, None, :]                         # (hb,Q,Q)
    y = jax.lax.dot_general(scores, x, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)    # (hb,Q,P)

    # inter-chunk: y += (C[q] · state_prev) e^{cum_q}.  B and C are
    # broadcast over the head block so both contractions are matmuls
    # batched over heads (a head-folded matmul would have to split the
    # lane dim back into (hb, P), which the TPU lowering refuses)
    Ch = jnp.broadcast_to(Cm[None], (nheads_blk, chunk, Cm.shape[-1]))
    Bh = jnp.broadcast_to(Bm[None], (nheads_blk, chunk, Bm.shape[-1]))
    state = state_s[...]                                           # (hb,P,S)
    yin = jax.lax.dot_general(Ch, state, (((2,), (2,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)  # (hb,Q,P)
    y = y + yin * jnp.exp(cum)[:, :, None]

    # state update: S' = e^{seg_end} S + Σ_t e^{seg_end - cum_t} dt_t x_t B_t
    w = jnp.exp(seg_end - cum) * dt                                # (hb,Q)
    xw = x * w[:, :, None]                                         # (hb,Q,P)
    upd = jax.lax.dot_general(xw, Bh, (((1,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)  # (hb,P,S)
    state_s[...] = state * jnp.exp(seg_end)[:, :, None] + upd

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_tpu(x, dt, A, B, C, *, chunk: int = 64, heads_blk: int = 8,
            interpret: bool = False):
    """x: (b, H, T, P); dt: (b, H, T); A: (H,); B, C: (b, T, S) (G=1).

    Returns y: (b, H, T, P).  T must divide by `chunk`, H by `heads_blk`.
    """
    b, H, T, P = x.shape
    S = B.shape[-1]
    if T % chunk or H % heads_blk:
        raise ValueError(
            f"seq len {T} must divide by chunk={chunk} and heads {H} by "
            f"heads_blk={heads_blk}")
    nc = T // chunk
    nhb = H // heads_blk

    # reshape for blocking: x (b, nhb, hb, nc, Q, P) via index maps instead
    kernel = functools.partial(_kernel, nheads_blk=heads_blk, chunk=chunk,
                               nchunks=nc)
    # the TPU tiles a block's last two dims by (8, 128) unless they span
    # the whole array dim: dt goes chunk-major (b, nc, H, Q) so a block's
    # last dim is a whole chunk, and A goes (H, 1) so its last dim is whole
    dt4 = dt.reshape(b, H, nc, chunk).transpose(0, 2, 1, 3)
    return pl.pallas_call(
        kernel,
        grid=(b, nhb, nc),
        in_specs=[
            pl.BlockSpec((1, heads_blk, chunk, P),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, heads_blk, chunk),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((heads_blk, 1), lambda bi, hi, ci: (hi, 0)),
            pl.BlockSpec((1, chunk, S), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, S), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, heads_blk, chunk, P),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((heads_blk, P, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt4, A[:, None], B, C)
