"""Attention: GQA with optional QKV bias and sliding window.

Three compute paths:

* ``attention_xla``      — blocked online-softmax (flash-style) in pure lax,
                           used for train/prefill.  Causal masking is applied
                           per block; the banded variant skips out-of-window
                           blocks entirely for SWA (honest linear FLOPs).
* ``decode_attention``   — single-query attention against a KV cache whose
                           sequence dim may be sharded over the "model" mesh
                           axis; written so GSPMD's partial-reduction rules
                           lower the softmax into the distributed
                           log-sum-exp combine (no cache all-gather).
* Pallas kernel          — repro.kernels.flash_attention, the TPU-target
                           path (see kernels/ops.py for dispatch).

Projections are kept *flat* (d → H·hd) so tensor-parallel sharding is a
plain column/row split; KV projections are replicated over the model axis
when num_kv_heads doesn't divide the TP degree (GQA kv < tp case).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from .layers import dense_init, apply_rope, _dtype

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, H, K, hd, dt = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd(), _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, H * hd), dt),
         "wk": dense_init(ks[1], (d, K * hd), dt),
         "wv": dense_init(ks[2], (d, K * hd), dt),
         "wo": dense_init(ks[3], (H * hd, d), dt)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((K * hd,), dt)
        p["bv"] = jnp.zeros((K * hd,), dt)
    return p


def qkv(p: dict, x, cfg: ModelConfig, positions=None, rope: bool = True):
    """x: (B, T, d) → q (B,T,H,hd), k/v (B,T,K,hd), rotary applied."""
    B, T, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd()
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, K, hd)
    v = v.reshape(B, T, K, hd)
    if rope:
        if positions is None:
            positions = jnp.arange(T)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# blocked online-softmax attention (training / prefill)
# ---------------------------------------------------------------------------

def _pick_block(T: int, target: int) -> int:
    """Largest divisor of T that is ≤ target (whisper's 1500-frame encoder
    isn't 512-divisible; blocks must tile the sequence exactly)."""
    b = min(target, T)
    while T % b:
        b -= 1
    return b


def _gqa_scores(qb, kb):
    """qb: (B,Tq,K,G,hd)  kb: (B,Tk,K,hd) → (B,K,G,Tq,Tk) fp32."""
    return jnp.einsum("btkgd,bskd->bkgts", qb, kb,
                      preferred_element_type=jnp.float32)


def attention_xla(q, k, v, *, causal: bool, window: int = 0,
                  q_offset: int = 0, block_q: int = 512, block_k: int = 512,
                  save_memory: bool = True, scale: float | None = None):
    """Flash-style blocked attention, pure lax (runs/lowers anywhere).

    q: (B, Tq, H, hd); k, v: (B, Tk, K, hd); H = K·G.
    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``window`` > 0 selects the banded path: for each q block only the k
    blocks intersecting [pos-window, pos] are touched — honest O(T·w) FLOPs.
    ``save_memory`` checkpoints each k-step so the backward recomputes the
    score block instead of storing nk fp32 (bq,bk) tiles per layer — the
    flash-attention trade, expressed at the lax level (the Pallas kernel
    does the same natively on TPU).  ``scale`` multiplies the scores
    (default ``hd ** -0.5``).
    Returns (B, Tq, H, hd) in q.dtype.
    """
    B, Tq, H, hd = q.shape
    Tk, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qs = (q * scale).reshape(B, Tq, K, G, hd)

    if window and window < Tk:
        return _attention_banded(qs, k, v, window=window, causal=causal,
                                 q_offset=q_offset, block_q=block_q)

    block_q = _pick_block(Tq, block_q)
    block_k = _pick_block(Tk, block_k)
    nq, nk = Tq // block_q, Tk // block_k

    kpos = jnp.arange(Tk)

    def q_block(qi):
        qb = lax.dynamic_slice_in_dim(qs, qi * block_q, block_q, axis=1)
        qpos = q_offset + qi * block_q + jnp.arange(block_q)

        def k_step(carry, ki):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, ki * block_k, block_k, axis=1)
            vb = lax.dynamic_slice_in_dim(v, ki * block_k, block_k, axis=1)
            s = _gqa_scores(qb, kb)                       # (B,K,G,bq,bk)
            kp = ki * block_k + jnp.arange(block_k)
            if causal:
                mask = qpos[:, None] >= kp[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgts,bskd->bkgtd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, K, G, block_q, hd), jnp.float32)
        step_fn = jax.checkpoint(k_step) if save_memory else k_step
        (m, l, acc), _ = lax.scan(step_fn, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,K,G,bq,hd)
        return jnp.moveaxis(out, 3, 1)                    # (B,bq,K,G,hd)

    outs = lax.map(q_block, jnp.arange(nq))               # (nq,B,bq,K,G,hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq, H, hd)
    return out.astype(q.dtype)


def _attention_banded(qs, k, v, *, window: int, causal: bool, q_offset: int,
                      block_q: int):
    """Sliding-window attention: each q block reads only its KV band.

    qs pre-scaled: (B, Tq, K, G, hd).  Band width = window + block_q rows of
    KV per q block — FLOPs are O(Tq·(window+block_q)), not O(Tq·Tk).
    """
    B, Tq, K, G, hd = qs.shape
    Tk = k.shape[1]
    block_q = _pick_block(Tq, block_q)
    nq = Tq // block_q
    band = window + block_q

    def q_block(qi):
        qb = lax.dynamic_slice_in_dim(qs, qi * block_q, block_q, axis=1)
        qpos = q_offset + qi * block_q + jnp.arange(block_q)
        start = jnp.clip(q_offset + qi * block_q - window, 0, max(Tk - band, 0))
        kb = lax.dynamic_slice_in_dim(k, start, min(band, Tk), axis=1)
        vb = lax.dynamic_slice_in_dim(v, start, min(band, Tk), axis=1)
        kp = start + jnp.arange(kb.shape[1])
        s = _gqa_scores(qb, kb)
        mask = kp[None, :] >= qpos[:, None] - window
        if causal:
            mask &= qpos[:, None] >= kp[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        out = jnp.einsum("bkgts,bskd->bkgtd", p.astype(vb.dtype), vb,
                         preferred_element_type=jnp.float32)
        out = out / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return jnp.moveaxis(out, 3, 1)                    # (B,bq,K,G,hd)

    outs = lax.map(q_block, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq, K * G, hd)
    return out.astype(k.dtype)


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, kcache, vcache, cache_len, *, window: int = 0,
                     scale: float | None = None):
    """q: (B, 1, H, hd); caches: (B, S, K, hd); cache_len: current length;
    ``scale`` multiplies the scores (default ``hd ** -0.5``).

    The cache's S dim may be sharded over the model axis — the max/sum
    reductions below are over that dim, which GSPMD lowers to local partial
    softmax stats + cross-shard combine (the distributed LSE pattern), never
    an all-gather of the cache.
    """
    B, _, H, hd = q.shape
    S, K = kcache.shape[1], kcache.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qh = (q[:, 0] * scale).reshape(B, K, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, kcache,
                   preferred_element_type=jnp.float32)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (B,))
    pos = jnp.arange(S)
    valid = pos[None, :] < cache_len[:, None]
    if window:
        valid = jnp.logical_and(valid,
                                pos[None, :] >= cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    num = jnp.einsum("bkgs,bskd->bkgd", p.astype(vcache.dtype), vcache,
                     preferred_element_type=jnp.float32)
    out = num / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return out.reshape(B, 1, H, hd).astype(q.dtype)
