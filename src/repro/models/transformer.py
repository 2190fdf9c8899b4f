"""Model assembly: one stack covering every assigned architecture family.

Families
  dense  — llama-style pre-norm blocks (GQA attn + [Sw]GLU MLP), scanned
  moe    — same skeleton with the MLP swapped for the capacity MoE
  ssm    — Mamba2 blocks only (attention-free)
  hybrid — Zamba2 (arXiv:2411.15242): a Mamba2 layer at every depth; at
           each of ``hybrid_layer_ids`` one of ``num_mem_blocks``
           weight-shared attention+MLP blocks, in turn, reads
           concat(h, token embeddings) and joins the Mamba layer's input
           through that application's own adapter and projection
  vlm    — dense backbone consuming [projected patch embeds | token embeds]
  audio  — Whisper backbone: bidirectional encoder over stub frame
           embeddings + causal decoder with cross-attention

All parameters for scanned layers are stacked along a leading L dim
(init via vmap over per-layer keys), so compile time is O(1) in depth and
FSDP/TP shardings apply uniformly.  Serving uses functional caches:
prefill threads them through the layer scan as scan xs/ys; decode reads
each layer of the stacked K/V cache, emits only the new rows, and writes
them into the cache in place after the scan (``_kv_write``).

Named scopes (``jax.named_scope``; metadata only, the compiled program is
unchanged) mark each op's layer in its HLO ``op_name``: ``attn`` (norm,
QKV, attention, output projection), ``kv_write`` inside it (the cache
write), ``mlp`` (the feed-forward block; in the hybrid's shared block also
its adapter and the application's projection), and from ``models/ssm.py``
``mamba`` (the whole Mamba2 mixer) with ``ssd`` inside it.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from . import layers as L
from . import attention as A
from . import ssm as S
from . import moe as M
from .blockstack import (BlockSpec, ShardedBlocks, ShardedStack,
                         block_stack_spec, register_block_stack, scan_stack,
                         scan_stack_cached)

# activation-sharding hints live in layers.py (shared with moe/ssm);
# re-exported here for the launch layer.
from .layers import activation_batch_axes, pin_act, pin_kv  # noqa: E402
from .parallel import parallel_ctx  # noqa: E402


def _pin(h):
    """Layer-boundary pin: batch axes + optional d_axis on the feature dim.

    Without this, GSPMD under FSDP params may flip activations to
    batch-replicated / d-sharded (verified: 16× activation memory on
    qwen110b train_4k); with d_axis set the saved-for-backward h stacks
    also shrink by the TP degree (Megatron-SP-along-d algebra).
    """
    return pin_act(h, shard_last=True)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_norm(cfg: ModelConfig, d: int):
    if cfg.norm == "layernorm":
        return L.init_layernorm(d, L._dtype(cfg))
    return L.init_rmsnorm(d, L._dtype(cfg))


def _init_attn_layer(key, cfg: ModelConfig, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    p = {"ln1": _init_norm(cfg, cfg.d_model),
         "attn": A.init_attention(ks[0], cfg),
         "ln2": _init_norm(cfg, cfg.d_model)}
    if cfg.family == "moe":
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    if cross:
        p["lnx"] = _init_norm(cfg, cfg.d_model)
        p["xattn"] = A.init_attention(ks[2], cfg)
    return p


def _init_mamba_layer(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": _init_norm(cfg, cfg.d_model),
            "mamba": S.init_mamba2(k1, cfg)}


def _init_hybrid_shared(key, cfg: ModelConfig) -> dict:
    """Zamba2's replicated leftovers: ``mem``, the ``num_mem_blocks``
    shared blocks (attention over concat(h, embeddings), 2·d wide in, and
    a gate/up MLP), and ``apps``, per application its MLP adapter and its
    d x d output projection."""
    d, H, hd, f, r = (cfg.d_model, cfg.num_heads, cfg.hd(), cfg.d_ff,
                      cfg.adapter_rank)
    dt = L._dtype(cfg)
    kb, ka = jax.random.split(key)

    def block(k):
        k = jax.random.split(k, 6)
        return {"ln_in": _init_norm(cfg, 2 * d),
                "attn": {"wq": L.dense_init(k[0], (2 * d, H * hd), dt),
                         "wk": L.dense_init(k[1], (2 * d, H * hd), dt),
                         "wv": L.dense_init(k[2], (2 * d, H * hd), dt),
                         "wo": L.dense_init(k[3], (H * hd, d), dt)},
                "ln_ff": _init_norm(cfg, d),
                "mlp": {"w_gate_up": L.dense_init(k[4], (d, 2 * f), dt),
                        "w_down": L.dense_init(k[5], (f, d), dt)}}

    def app(k):
        k = jax.random.split(k, 3)
        return {"adapter_in": L.dense_init(k[0], (d, r), dt),
                "adapter_out": L.dense_init(k[1], (r, 2 * f), dt),
                "linear": L.dense_init(k[2], (d, d), dt)}

    return {"mem": [block(k) for k in
                    jax.random.split(kb, cfg.num_mem_blocks)],
            "apps": [app(k) for k in
                     jax.random.split(ka, len(cfg.hybrid_layer_ids))]}


def _stacked(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_model(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {"embed": L.init_embed(ks[0], cfg),
                              "final_norm": _init_norm(cfg, cfg.d_model)}
    if cfg.family in ("dense", "vlm", "moe"):
        params["blocks"] = _stacked(lambda k: _init_attn_layer(k, cfg),
                                    ks[1], cfg.num_layers)
    elif cfg.family == "ssm":
        params["blocks"] = _stacked(lambda k: _init_mamba_layer(k, cfg),
                                    ks[1], cfg.num_layers)
    elif cfg.family == "hybrid":
        params["blocks"] = _stacked(lambda k: _init_mamba_layer(k, cfg),
                                    ks[1], cfg.num_layers)
        params["shared_attn"] = _init_hybrid_shared(ks[2], cfg)
    elif cfg.family == "audio":
        params["blocks"] = _stacked(
            lambda k: _init_attn_layer(k, cfg, cross=True), ks[1],
            cfg.num_layers)
        params["encoder"] = {
            "blocks": _stacked(lambda k: _init_attn_layer(k, cfg), ks[3],
                               cfg.encoder_layers),
            "final_norm": _init_norm(cfg, cfg.d_model),
            "pos": L.dense_init(ks[4], (cfg.encoder_seq, cfg.d_model),
                                L._dtype(cfg), scale=0.01),
        }
    else:
        raise ValueError(cfg.family)
    if cfg.family == "vlm":
        params["vis_proj"] = L.dense_init(ks[5], (cfg.d_model, cfg.d_model),
                                          L._dtype(cfg))
    return params


# ---------------------------------------------------------------------------
# blocks (shared by the no-cache and cached paths)
# ---------------------------------------------------------------------------

def _norm(cfg, p, x):
    return L.apply_norm(p, x, cfg.norm_eps)


def _attn_noncache(lp, h, cfg: ModelConfig, *, causal: bool, positions,
                   window: int, kv=None):
    """Full-sequence attention (train / encoder / cross with given kv)."""
    with jax.named_scope("attn"):
        hn = _norm(cfg, lp["ln1"] if kv is None else lp["lnx"], h)
        ap = lp["attn"] if kv is None else lp["xattn"]
        if kv is None:
            q, k, v = A.qkv(ap, hn, cfg, positions=positions, rope=True)
        else:
            q, _, _ = A.qkv(ap, hn, cfg, positions=positions, rope=False)
            k, v = kv
        o = A.attention_xla(q, k, v, causal=causal, window=window)
        o = o.reshape(*o.shape[:2], -1) @ ap["wo"]
        return h + o


def _ffn(lp, h, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        hn = _norm(cfg, lp["ln2"], h)
        ctx = parallel_ctx()
        if "moe" in lp:
            if ctx.ep and ctx.ep_comm is not None:
                out, aux = M.moe_block_ep(lp["moe"], hn, cfg,
                                          comm=ctx.ep_comm,
                                          ep_blocks=ctx.ep_blocks,
                                          strategy=ctx.ep_strategy)
            else:
                out, aux = M.moe_block(lp["moe"], hn, cfg)
            return h + out, aux
        if ctx.tp > 1 and ctx.tp_comm is not None:
            tp_mlp = L.mlp_tp_reduce if ctx.tp_variant == "reduce" \
                else L.mlp_tp
            return h + tp_mlp(lp["mlp"], hn, cfg, comm=ctx.tp_comm,
                              strategy=ctx.tp_strategy), 0.0
        return h + L.mlp(lp["mlp"], hn, cfg), 0.0


def _dense_block(lp, h, cfg: ModelConfig, *, positions, enc_out=None):
    causal = True
    h = _attn_noncache(lp, h, cfg, causal=causal, positions=positions,
                       window=cfg.sliding_window)
    if enc_out is not None and "xattn" in lp:
        k, v = _cross_kv(lp["xattn"], enc_out, cfg)
        h = _attn_noncache(lp, h, cfg, causal=False, positions=positions,
                           window=0, kv=(k, v))
    h, aux = _ffn(lp, h, cfg)
    return h, aux


def _cross_kv(ap, enc_out, cfg: ModelConfig):
    Bz, Te, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.hd()
    k = (enc_out @ ap["wk"]).reshape(Bz, Te, K, hd)
    v = (enc_out @ ap["wv"]).reshape(Bz, Te, K, hd)
    if "bk" in ap:
        k = k + ap["bk"].reshape(K, hd)
        v = v + ap["bv"].reshape(K, hd)
    return k, v


def _mamba_block(lp, h, cfg: ModelConfig, state=None):
    hn = _norm(cfg, lp["ln1"], h)
    out, new_state = S.mamba2_block(lp["mamba"], hn, cfg, state=state)
    return h + out, new_state


# families whose layer stack is one lax.scan over params["blocks"] — the
# shape ZeRO-3 sharding (ShardedStack, repro.models.blockstack) can
# substitute into directly; ssm/hybrid scan through their own bodies
_SCANNED_FAMILIES = ("dense", "vlm", "moe", "audio")


# ---------------------------------------------------------------------------
# forward (no cache): training and encoder passes
# ---------------------------------------------------------------------------

def _maybe_remat(f, policy: str):
    if policy == "none":
        return f
    if policy == "full":
        return jax.checkpoint(f)
    if policy == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(policy)


def _encoder_forward(params, cfg: ModelConfig, frames, remat: str = "none"):
    """Whisper encoder over stub frame embeddings (B, Te, d)."""
    enc = params["encoder"]
    h = frames + enc["pos"][None, :frames.shape[1]]
    positions = jnp.arange(frames.shape[1])[None]

    def body(h, lp):
        h = _attn_noncache(lp, h, cfg, causal=False, positions=positions,
                           window=0)
        h, _ = _ffn(lp, h, cfg)
        return _pin(h), None

    body = _maybe_remat(body, remat)
    h, _ = lax.scan(body, h, enc["blocks"])
    return _norm(cfg, enc["final_norm"], h)


def _embed_inputs(params, cfg: ModelConfig, tokens, extra_embeds):
    """Token embeds, with VLM patch prefix when provided."""
    h = L.embed(params["embed"], tokens)
    if cfg.family == "vlm":
        if extra_embeds is None:
            raise ValueError("vlm needs patch embeddings")
        vis = extra_embeds @ params["vis_proj"]
        h = jnp.concatenate([vis.astype(h.dtype), h], axis=1)
    return h


def model_forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
                  remat: str = "none"):
    """Full forward to logits; `extra_embeds` = patches (vlm) / frames (audio).

    Returns (logits (B, T_total, V), aux_loss).
    """
    enc_out = None
    if cfg.family == "audio":
        if extra_embeds is None:
            raise ValueError("audio needs frame embeddings")
        enc_out = _encoder_forward(params, cfg, extra_embeds, remat)
        h = L.embed(params["embed"], tokens)
    else:
        h = _embed_inputs(params, cfg, tokens, extra_embeds)
    h = _pin(h)
    Bz, T, _ = h.shape
    positions = jnp.arange(T)[None]
    aux_total = jnp.zeros((), jnp.float32)

    if isinstance(params.get("blocks"), ShardedStack):
        # ONE code path for every lane-capable family: the registered
        # BlockSpec supplies the per-layer body, scan_stack supplies the
        # prefetch/blocking/regather layer scan (models/blockstack.py)
        spec = block_stack_spec(cfg)
        body = spec.make_body(cfg, params, positions=positions,
                              enc_out=enc_out, remat=remat, x0=h)
        h, aux_ys = scan_stack(params["blocks"], h, body)
        aux_total = jnp.sum(aux_ys)

    elif cfg.family in _SCANNED_FAMILIES:
        # aux losses leave via ys, not the carry (a mixed-dtype carry made
        # XLA:CPU stack an f32 copy of every layer's h for the backward)
        def body(h, lp):
            h, a = _dense_block(lp, h, cfg, positions=positions,
                                enc_out=enc_out)
            return _pin(h), a
        body = _maybe_remat(body, remat)
        h, aux_ys = lax.scan(body, h, params["blocks"])
        aux_total = jnp.sum(aux_ys)

    elif cfg.family == "ssm":
        def body(h, lp):
            h, _ = _mamba_block(lp, h, cfg)
            return _pin(h), None
        body = _maybe_remat(body, remat)
        h, _ = lax.scan(body, h, params["blocks"])

    elif cfg.family == "hybrid":
        h = _hybrid_forward(params, cfg, h, positions, remat)

    h = _norm(cfg, params["final_norm"], h)
    logits = L.unembed(params["embed"], h)
    return logits, aux_total


# ---------------------------------------------------------------------------
# hybrid (Zamba2): one layer body for the forward, lane_zero3 and serving
# ---------------------------------------------------------------------------

def _hybrid_apps(cfg: ModelConfig):
    """(L,) int32: 0 at a Mamba-only layer, j + 1 at layer
    ``hybrid_layer_ids[j]``, the j-th application of a shared block."""
    ids = list(cfg.hybrid_layer_ids)
    if ids != sorted(set(ids)) or (ids and not 0 <= ids[0] <= ids[-1]
                                   < cfg.num_layers):
        raise ValueError(f"{cfg.name}: hybrid_layer_ids {ids} must be "
                         f"increasing layer indices below {cfg.num_layers}")
    table = [0] * cfg.num_layers
    for j, i in enumerate(ids):
        table[i] = j + 1
    return jnp.asarray(table, jnp.int32)


def _hybrid_scale(cfg: ModelConfig) -> float:
    """Zamba2's attention scaling, ``(head_dim / 2) ** -0.5`` as in the
    published modeling code: its heads are twice as wide as the hidden
    size gives per head, the input being concat(h, embeddings)."""
    return (cfg.hd() / 2) ** -0.5


def _shared_block(cfg: ModelConfig, shared, j: int, h, x0, positions,
                  attend):
    """Zamba2's shared transformer block at application ``j``: block
    ``j % num_mem_blocks`` (A, B, A, ...) over ``u = RMSNorm(concat(h,
    x0))``, attention, ``a = RMSNorm(attention output)``, and the MLP
    ``down(gelu(g) * v)`` with ``[g, v] = W_gu a + B_j (A_j a)`` (the
    application's own adapter), with no residual; then the application's
    projection.  Returns ``t`` (B, T, d), which joins the Mamba layer's
    input, and what ``attend(j, q, k, v) -> (o, extra)`` (the path's
    attention) returns beside the attention output."""
    blk = shared["mem"][j % len(shared["mem"])]
    app = shared["apps"][j]
    with jax.named_scope("attn"):
        u = _norm(cfg, blk["ln_in"], jnp.concatenate([h, x0], axis=-1))
        q, k, v = A.qkv(blk["attn"], u, cfg, positions=positions, rope=True)
        o, extra = attend(j, q, k, v)
        o = o.reshape(*o.shape[:2], -1) @ blk["attn"]["wo"]
    with jax.named_scope("mlp"):
        a = _norm(cfg, blk["ln_ff"], o)
        gu = a @ blk["mlp"]["w_gate_up"] \
            + (a @ app["adapter_in"]) @ app["adapter_out"]
        g, up = jnp.split(gu, 2, axis=-1)
        m = (L._act(cfg.act)(g) * up) @ blk["mlp"]["w_down"]
        return m @ app["linear"], extra


def _hybrid_layer(cfg: ModelConfig, lp, h, t=None, *, state=None,
                  length=None):
    """Zamba2's layer: ``h + Mamba2(RMSNorm(h + t))``, ``t`` the shared
    block's output at a hybrid layer (:func:`_shared_block`) and None
    elsewhere; the residual is the ``h`` before ``t``.  ``state``/
    ``length``: the layer's serving state and a prefill's true lengths
    (:func:`repro.models.ssm.mamba2_block`).  Returns ``(h, new state)``."""
    hn = _norm(cfg, lp["ln1"], h if t is None else h + t)
    out, st = S.mamba2_block(lp["mamba"], hn, cfg, state=state,
                             length=length)
    return h + out, st


def _hybrid_forward(params, cfg: ModelConfig, h, positions, remat):
    """The replicated forward: the lane_zero3 per-layer body
    (:func:`_hybrid_stack_body`) scanned over the layer stack."""
    body = _hybrid_stack_body(cfg, params, positions=positions, enc_out=None,
                              remat=remat, x0=h)
    h, _ = lax.scan(lambda h, x: body(h, *x), h,
                    (params["blocks"], jnp.arange(cfg.num_layers)))
    return h


# ---------------------------------------------------------------------------
# block-stack specs: how each family rides the ZeRO-3 sharded stack
# (registered through the repro.comm registry seam; the machinery lives in
# models/blockstack.py, the zero3 step resolves specs via block_stack_spec)
# ---------------------------------------------------------------------------

def _scanned_stack_body(cfg, params, *, positions, enc_out, remat, x0=None):
    """Per-layer body of the scanned attention families (dense/vlm/moe/
    audio): identical math to the replicated layer scan.

    Under expert-parallel ``lane_zero3`` the expert weights live OUTSIDE
    the flat stack in a never-gathered (L, E/p, ...) local master
    (``ParallelContext.ep_experts``); layer i's row is sliced out here
    and merged into ``lp["moe"]`` so the block math below is untouched.
    """
    def body(h, lp, i):
        experts = parallel_ctx().ep_experts
        if experts is not None and "moe" in lp:
            row = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                experts)
            lp = {**lp, "moe": {**lp["moe"], **row}}
        h, a = _dense_block(lp, h, cfg, positions=positions,
                            enc_out=enc_out)
        return _pin(h), a
    return _maybe_remat(body, remat)


def _ssm_stack_body(cfg, params, *, positions, enc_out, remat, x0=None):
    """Mamba2 SSD scan bodies as the sharded layer unit."""
    def body(h, lp, i):
        h, _ = _mamba_block(lp, h, cfg)
        return _pin(h), jnp.zeros((), jnp.float32)
    return _maybe_remat(body, remat)


def _hybrid_stack_body(cfg, params, *, positions, enc_out, remat, x0=None):
    """Zamba2 as a flat per-layer scan (:func:`_hybrid_layer`), ``x0`` the
    token embeddings the shared blocks read.  The shared blocks, adapters
    and projections (``params["shared_attn"]``) stay replicated: a block
    runs at several layers, so sharding it would re-gather the same bytes.
    The remat cell is the per-layer body only, and the prefetch gather
    stays OUTSIDE it, so a backward recompute re-runs the block math but
    never the gather (pinned by the gather-count HLO case)."""
    shared = params["shared_attn"]
    scale = _hybrid_scale(cfg)

    def attend(j, q, k, v):
        return A.attention_xla(q, k, v, causal=True,
                               window=cfg.sliding_window, scale=scale), None

    def body(h, lp, i):
        # the layer's application, or none: lax.switch on its traced index
        apps = [lambda j=j: _shared_block(cfg, shared, j, h, x0, positions,
                                          attend)[0]
                for j in range(len(cfg.hybrid_layer_ids))]
        t = lax.switch(_hybrid_apps(cfg)[i], [lambda: jnp.zeros_like(h)]
                       + apps)
        h, _ = _hybrid_layer(cfg, lp, h, t)
        return _pin(h), jnp.zeros((), jnp.float32)
    return _maybe_remat(body, remat)


@register_block_stack("dense")
@register_block_stack("vlm")
@register_block_stack("audio")
def _block_stack_attn(cfg: ModelConfig) -> BlockSpec:
    """Scanned attention families: the (L, ...) block stack is the
    sharding unit; embed/final_norm (+ vis_proj / encoder) ride as the
    extras pseudo-layer.  vlm/audio forwards consume extra_embeds
    (patches / frames) the training driver does not synthesize, so
    driver-level sweeps skip them (family_smoke_archs)."""
    return BlockSpec(family=cfg.family, make_body=_scanned_stack_body,
                     needs_extra_embeds=cfg.family in ("vlm", "audio"))


@register_block_stack("moe")
def _block_stack_moe(cfg: ModelConfig) -> BlockSpec:
    """MoE: same scanned skeleton, but the per-layer flat vector is
    dominated by the stacked (E, d, f) expert tensors, so the 1/p
    stripes slice through the experts — the experts are the sharding
    unit, exactly the payload ZeRO-3 exists for."""
    return BlockSpec(family="moe", make_body=_scanned_stack_body)


@register_block_stack("ssm")
def _block_stack_ssm(cfg: ModelConfig) -> BlockSpec:
    return BlockSpec(family="ssm", make_body=_ssm_stack_body)


@register_block_stack("hybrid")
def _block_stack_hybrid(cfg: ModelConfig) -> BlockSpec:
    """Mamba2 backbone sharded 1/p; the weight-shared blocks with the
    per-application adapters and projections stay replicated
    (``replicated_keys``) and their gradients sync through the bucketed
    lane path."""
    return BlockSpec(family="hybrid", make_body=_hybrid_stack_body,
                     replicated_keys=("shared_attn",))


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, tokens, labels, *, extra_embeds=None,
            remat: str = "none", aux_weight: float = 0.01):
    """Next-token CE; labels = -100 are masked.  Returns scalar fp32 loss."""
    logits, aux = model_forward(params, cfg, tokens,
                                extra_embeds=extra_embeds, remat=remat)
    # VLM prefixes add vision tokens in front: loss only over text positions
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # gold logit via one-hot contraction, NOT take_along_axis: a gather
    # along the vocab dim would force GSPMD to all-gather the (B,T,V)
    # logits across the "model" axis; the masked reduction stays sharded.
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    onehot = (vocab_iota == safe[..., None])
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    ce = (logz - gold) * mask
    loss = ce.sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + aux_weight * aux


# ---------------------------------------------------------------------------
# serving: caches, prefill, decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeState:
    """Functional serving state (a pytree)."""
    cache: Any                 # per-family structure, stacked over layers
    length: Any                # (B,) int32 valid lengths
    enc_kv: Any = None         # audio: per-layer cross K/V (stacked)

    def tree_flatten(self):
        return (self.cache, self.length, self.enc_kv), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    ServeState, lambda s: s.tree_flatten(),
    lambda aux, c: ServeState(*c))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Any:
    """Stacked per-layer cache; KV seq dim is later sharded over "model"."""
    K, hd, Lr = cfg.num_kv_heads, cfg.hd(), cfg.num_layers
    kv = lambda n: {"k": jnp.zeros((n, batch, max_seq, K, hd), dtype),
                    "v": jnp.zeros((n, batch, max_seq, K, hd), dtype)}
    if cfg.family in _SCANNED_FAMILIES:
        return kv(Lr)
    if cfg.family == "ssm":
        st = S.init_mamba_state(cfg, batch)
        return jax.tree.map(
            lambda a: jnp.zeros((Lr, *a.shape), a.dtype), st)
    if cfg.family == "hybrid":
        st = S.init_mamba_state(cfg, batch)
        return {
            "mamba": jax.tree.map(
                lambda a: jnp.zeros((Lr, *a.shape), a.dtype), st),
            "attn": kv(len(cfg.hybrid_layer_ids)),   # one per application
        }
    raise ValueError(cfg.family)


def _seq_sharded() -> bool:
    """Whether the serve-cache layout (``pin_kv``) shards the cache's S dim:
    a read or write at a traced position there could make GSPMD gather the
    cache, so the decode keeps to one-hot selects over S."""
    spec = L._ACT_KV.get()
    return spec is not None and spec[1] is not None


def _kv_last_rows(cache):
    """{"k","v"} (n, B, K, hd): each layer's and slot's row at position
    S-1 of the stacked cache as the decode step finds it, for
    :func:`_kv_write` to write back where a slot is at or past S.  None
    where the S dim is sharded (the whole-cache select there needs none)."""
    if _seq_sharded():
        return None
    return {n: cache[n][:, :, -1] for n in ("k", "v")}


def _kv_write(cache, rows, length):
    """Write the decode step's new rows ``rows`` {"k","v"} (n, B, K, hd)
    into the stacked cache (n, B, S, K, hd) at position ``length[b]`` of
    each slot, every layer at once, after the layer loop.

    One dynamic_update_slice per slot, in place in the (donated) cache: B
    writes of n rows, not a rewrite of the stack.  A straight chain of
    them, not a scatter: the chip stores the cache with S minor, and a
    scatter (or a loop, a cond, a gather) made the compiler relayout, and
    so copy, the whole cache.  A slot at or past S
    (idle slots keep counting ``length`` up) has the slice's start clamped
    to S-1, and its rows there are the ones it found (:func:`_kv_last_rows`
    via the layer body), so nothing changes.  Nothing reads the cache
    once the writes begin, so the compiler needs no copy of it.  Where the
    S dim is sharded, a one-hot select over the whole cache instead.
    """
    S = cache["k"].shape[2]
    if _seq_sharded():
        hot = (jnp.arange(S) == length[:, None])[None, :, :, None, None]
        return {n: jnp.where(hot, rows[n][:, :, None].astype(a.dtype), a)
                for n, a in cache.items()}
    pos = jnp.minimum(length, S - 1)
    out = {}
    for n, a in cache.items():
        for b in range(length.shape[0]):
            a = lax.dynamic_update_slice(
                a, rows[n][:, b, None, None].astype(a.dtype),
                (0, b, pos[b], 0, 0))
        out[n] = a
    return out


def _decode_attend(q, k, v, lc, length, *, last, window: int,
                   scale: Optional[float] = None):
    """The decode's attention of q (B,1,H,hd) against one layer's cache
    ``lc`` {"k","v"} (B,S,K,hd) with the new k/v row selected in at
    ``length``.  Returns the output and the new rows {"k","v"} (B,K,hd)
    for :func:`_kv_write` (where a slot is at or past S, its row of
    ``last``, :func:`_kv_last_rows`)."""
    Smax = lc["k"].shape[1]
    # one-hot select at per-row `length` (GSPMD-safe on a sharded S
    # dim; pure select — an arithmetic blend promoted the cache to
    # fp32 on the CPU backend)
    with jax.named_scope("kv_write"):
        hot = (jnp.arange(Smax)[None, :] == length[:, None])  # (B,S)
        newk = pin_kv(jnp.where(hot[..., None, None],
                                k.astype(lc["k"].dtype), lc["k"]))
        newv = pin_kv(jnp.where(hot[..., None, None],
                                v.astype(lc["v"].dtype), lc["v"]))
        newc = {"k": k[:, 0].astype(lc["k"].dtype),
                "v": v[:, 0].astype(lc["v"].dtype)}
        if last is not None:
            past = (length >= Smax)[:, None, None]
            newc = {n: jnp.where(past, last[n], r)
                    for n, r in newc.items()}
    o = A.decode_attention(q, newk, newv, length + 1, window=window,
                           scale=scale)
    return o, newc


def _attn_cached(lp, h, cfg: ModelConfig, lc, length, *, prefill: bool,
                 enc_kv=None, last=None):
    """Attention with cache read/write.  h: (B,T,d); lc: {"k","v"} (B,S,K,hd).

    prefill: writes positions [0, T) and attends within the new block;
             returns the new ``lc``.
    decode:  T == 1, attends to the cache with the new row selected in at
             ``length``, and returns only the new rows {"k","v"} (B,K,hd)
             (where a slot is at or past S, its row of ``last``, this
             layer's :func:`_kv_last_rows`) for :func:`_kv_write`.
    """
    Bz, T, _ = h.shape
    positions = (jnp.arange(T)[None] if prefill else length[:, None])
    with jax.named_scope("attn"):
        hn = _norm(cfg, lp["ln1"], h)
        q, k, v = A.qkv(lp["attn"], hn, cfg, positions=positions, rope=True)
        if prefill:
            with jax.named_scope("kv_write"):
                newk = pin_kv(lax.dynamic_update_slice_in_dim(
                    lc["k"], pin_kv(k.astype(lc["k"].dtype)), 0, axis=1))
                newv = pin_kv(lax.dynamic_update_slice_in_dim(
                    lc["v"], pin_kv(v.astype(lc["v"].dtype)), 0, axis=1))
            newc = {"k": newk, "v": newv}
            o = A.attention_xla(q, k, v, causal=True,
                                window=cfg.sliding_window)
        else:
            o, newc = _decode_attend(q, k, v, lc, length, last=last,
                                     window=cfg.sliding_window)
        o = o.reshape(Bz, T, -1) @ lp["attn"]["wo"]
        h = h + o
        if enc_kv is not None and "xattn" in lp:
            hn = _norm(cfg, lp["lnx"], h)
            qx, _, _ = A.qkv(lp["xattn"], hn, cfg, positions=positions,
                             rope=False)
            o = A.decode_attention(qx, enc_kv["k"], enc_kv["v"],
                                   jnp.full((Bz,), enc_kv["k"].shape[1])) \
                if not prefill else \
                A.attention_xla(qx, enc_kv["k"], enc_kv["v"], causal=False)
            h = h + o.reshape(Bz, T, -1) @ lp["xattn"]["wo"]
    h, _ = _ffn(lp, h, cfg)
    return h, newc


def _scan_enc_kv(params, cfg, enc_out):
    def body(_, lp):
        k, v = _cross_kv(lp["xattn"], enc_out, cfg)
        return None, {"k": k, "v": v}
    _, kv = lax.scan(body, None, params["blocks"])
    return kv


def _select_row(h, pos):
    """(B, T, d) -> (B, 1, d): row ``pos[b]`` of each batch element, with a
    traced per-row ``pos``, via one-hot select (no gather — GSPMD-safe on
    a sharded T dim; exact, since exactly one position is hot)."""
    hot = (jnp.arange(h.shape[1])[None, :] == pos[:, None])
    return jnp.sum(jnp.where(hot[..., None], h, jnp.zeros((), h.dtype)),
                   axis=1, keepdims=True).astype(h.dtype)


def prefill(params, cfg: ModelConfig, tokens, cache, *, extra_embeds=None,
            true_len=None):
    """Run the prompt; fill caches.  Returns (logits_last, state).

    ``true_len`` (scalar or (B,) int) marks the valid prompt length when
    ``tokens`` is right-padded to a bucket: the returned logits are taken
    at the LAST TRUE position (``prefix + true_len - 1``, prefix = the
    vlm vision tokens) instead of the bucket's last position — the seed
    engine conditioned the first generated token on trailing pad — and
    ``state.length`` is ``prefix + true_len``, so decode overwrites the
    pad region progressively and attention never reads past it.  For
    the recurrent families (ssm/hybrid) it also keeps the padding out of
    the recurrence: past it ``dt`` and the SSM input are 0, so the state
    passes through unchanged, and the conv state is read at the true end
    (:func:`repro.models.ssm.mamba2_block`).

    ``params["blocks"]`` may be a :class:`ShardedStack` (zero3 hosting):
    the cached layer scan then runs through ``scan_stack_cached`` with the
    same one-layer prefetch as training, and the audio cross K/V are
    computed inside the body (the encoder output is replicated; the
    per-layer projections live in the sharded stack).
    """
    sharded = isinstance(params.get("blocks"), ShardedStack)
    enc_kv = None
    enc_out = None
    if cfg.family == "audio":
        enc_out = _encoder_forward(params, cfg, extra_embeds)
        if not sharded:
            enc_kv = _scan_enc_kv(params, cfg, enc_out)
        h = L.embed(params["embed"], tokens)
    else:
        h = _embed_inputs(params, cfg, tokens, extra_embeds)
    Bz, T, _ = h.shape
    length0 = jnp.zeros((Bz,), jnp.int32)
    tl = None if true_len is None else jnp.broadcast_to(
        jnp.asarray(true_len, jnp.int32), (Bz,))

    if sharded:
        if cfg.family == "audio":
            def body(h, lp, lc):
                k, v = _cross_kv(lp["xattn"], enc_out, cfg)
                ekv = {"k": k, "v": v}
                h, newc = _attn_cached(lp, h, cfg, lc, length0,
                                       prefill=True, enc_kv=ekv)
                return h, (newc, ekv)
            h, (newcache, enc_kv) = scan_stack_cached(
                params["blocks"], h, cache, body)
        elif cfg.family in _SCANNED_FAMILIES:
            def body(h, lp, lc):
                h, newc = _attn_cached(lp, h, cfg, lc, length0,
                                       prefill=True)
                return h, newc
            h, newcache = scan_stack_cached(params["blocks"], h, cache,
                                            body)
        elif cfg.family == "ssm":
            def body(h, lp, lc):
                hn = _norm(cfg, lp["ln1"], h)
                out, st = S.mamba2_block(lp["mamba"], hn, cfg, state=lc,
                                         length=tl)
                return h + out, st
            h, newcache = scan_stack_cached(params["blocks"], h, cache,
                                            body)
        else:
            raise ValueError(
                f"family {cfg.family!r} cannot serve from a ShardedStack "
                f"(its replicated shared blocks and per-application cache "
                f"are not in the flat cached layer scan); host it "
                f"replicated")
    elif cfg.family in _SCANNED_FAMILIES:
        xs = (params["blocks"], cache) if enc_kv is None else \
             (params["blocks"], cache, enc_kv)

        def body(h, lpc):
            lp, lc = lpc[0], lpc[1]
            ekv = lpc[2] if len(lpc) == 3 else None
            h, newc = _attn_cached(lp, h, cfg, lc, length0, prefill=True,
                                   enc_kv=ekv)
            return h, newc

        h, newcache = lax.scan(body, h, xs)
    elif cfg.family == "ssm":
        def body(h, lpc):
            lp, lc = lpc
            hn = _norm(cfg, lp["ln1"], h)
            out, st = S.mamba2_block(lp["mamba"], hn, cfg, state=lc,
                                     length=tl)
            return h + out, st
        h, newcache = lax.scan(body, h, (params["blocks"], cache))
    elif cfg.family == "hybrid":
        h, newcache = _hybrid_cached(params, cfg, h, cache, length0,
                                     prefill=True, true_len=tl)
    else:
        raise ValueError(cfg.family)

    h = _norm(cfg, params["final_norm"], h)
    prefix = T - tokens.shape[1]            # vlm vision tokens, else 0
    if true_len is None:
        h_last = h[:, -1:]
        length = jnp.full((Bz,), T, jnp.int32)
    else:
        h_last = _select_row(h, prefix + tl - 1)
        length = prefix + tl
    logits = L.unembed(params["embed"], h_last)
    state = ServeState(cache=newcache, length=length, enc_kv=enc_kv)
    return logits, state


def decode_step(params, cfg: ModelConfig, token, state: ServeState):
    """One token for every sequence.  token: (B, 1) int32.

    The attention families read each layer's cache from the stacked cache
    (the layer index comes from xs), emit only the layer's new rows from
    the layer scan, and write all of them into the donated cache in place
    after it (:func:`_kv_write`): the step moves B x layers rows into the
    cache, not the cache.

    Like :func:`prefill`, ``params["blocks"]`` may be a
    :class:`ShardedStack`: layer i+1's 1/p weight gather is issued
    alongside layer i's cached attention (``scan_stack_cached``) — the
    decode-side incarnation of the §5 prefetch pipeline.
    """
    h = L.embed(params["embed"], token)
    length = state.length
    enc_kv = state.enc_kv

    def attn_body(h, lp, x):
        i, last, ekv = x
        lc = {n: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
              for n, a in state.cache.items()}
        return _attn_cached(lp, h, cfg, lc, length, prefill=False,
                            enc_kv=ekv, last=last)

    if cfg.family in _SCANNED_FAMILIES:
        xs = (jnp.arange(cfg.num_layers), _kv_last_rows(state.cache),
              enc_kv)
        if isinstance(params.get("blocks"), ShardedStack):
            h, rows = scan_stack_cached(params["blocks"], h, xs, attn_body)
        else:
            h, rows = lax.scan(lambda h, x: attn_body(h, *x), h,
                               (params["blocks"], xs))
        with jax.named_scope("attn"), jax.named_scope("kv_write"):
            newcache = _kv_write(state.cache, rows, length)
    elif isinstance(params.get("blocks"), ShardedStack):
        if cfg.family != "ssm":
            raise ValueError(
                f"family {cfg.family!r} cannot serve from a ShardedStack "
                f"(its replicated shared blocks and per-application cache "
                f"are not in the flat cached layer scan); host it "
                f"replicated")

        def body(h, lp, lc):
            hn = _norm(cfg, lp["ln1"], h)
            out, st = S.mamba2_block(lp["mamba"], hn, cfg, state=lc)
            return h + out, st
        h, newcache = scan_stack_cached(params["blocks"], h, state.cache,
                                        body)
    elif cfg.family == "ssm":
        def body(h, lpc):
            lp, lc = lpc
            hn = _norm(cfg, lp["ln1"], h)
            out, st = S.mamba2_block(lp["mamba"], hn, cfg, state=lc)
            return h + out, st
        h, newcache = lax.scan(body, h, (params["blocks"], state.cache))
    elif cfg.family == "hybrid":
        h, newcache = _hybrid_cached(params, cfg, h, state.cache, length,
                                     prefill=False)
    else:
        raise ValueError(cfg.family)

    h = _norm(cfg, params["final_norm"], h)
    logits = L.unembed(params["embed"], h)
    new_state = ServeState(cache=newcache, length=length + 1,
                           enc_kv=enc_kv)
    return logits, new_state


def _hybrid_cached(params, cfg: ModelConfig, h, cache, length, *, prefill,
                   true_len=None):
    """Zamba2 serving pass, in program order: at each hybrid layer its
    application of a shared block, then a scan over that layer and the
    Mamba-only layers up to the next one, each layer's weights and state
    read by index from the stacks and its new state written back in place
    (slices of the stacks taken outside the loop were copied whole on the
    chip).  Each application reads its own (B, S, K, hd) slice of the
    (applications, B, S, K, hd) cache at a static index, never inside a
    loop or a conditional (either made the chip's compiler copy the whole
    stack), and gives its K/V rows: the prefill's every prompt position,
    written at position 0 after the pass; the decode's new row per slot,
    attended with it selected in as :func:`decode_step` does and written
    in place through :func:`_kv_write`.  ``true_len``: the prefill's true
    prompt lengths."""
    x0, kv, shared = h, cache["attn"], params["shared_attn"]
    scale = _hybrid_scale(cfg)
    if prefill:
        positions = jnp.arange(h.shape[1])[None]

        def attend(j, q, k, v):
            o = A.attention_xla(q, k, v, causal=True,
                                window=cfg.sliding_window, scale=scale)
            return o, {"k": k.astype(kv["k"].dtype),
                       "v": v.astype(kv["v"].dtype)}
    else:
        positions = length[:, None]
        last = _kv_last_rows(kv)

        def attend(j, q, k, v):
            lc = {n: a[j] for n, a in kv.items()}
            lj = None if last is None else {n: a[j] for n, a in last.items()}
            return _decode_attend(q, k, v, lc, length, last=lj,
                                  window=cfg.sliding_window, scale=scale)

    def run(h, t, mc, lo, hi):
        """Layers lo..hi-1, each read by index from the stacks of weights
        and states, its new state written back in place; ``t`` joins the
        first one's input."""
        def body(carry, i):
            h, t, mc = carry
            at = lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            h, st = _hybrid_layer(cfg, jax.tree.map(at, params["blocks"]), h,
                                  t, state=jax.tree.map(at, mc),
                                  length=true_len)
            mc = jax.tree.map(lambda a, s: lax.dynamic_update_index_in_dim(
                a, s.astype(a.dtype), i, 0), mc, st)
            return (h, jnp.zeros_like(t), mc), None
        (h, _, mc), _ = lax.scan(body, (h, t, mc), jnp.arange(lo, hi))
        return h, mc

    # segment k runs layers bounds[k]..bounds[k+1]-1; from the second on
    # it opens with a hybrid layer, whose application comes first
    bounds = (0, *cfg.hybrid_layer_ids, cfg.num_layers)
    mc, rows, t = cache["mamba"], [], jnp.zeros_like(h)
    for k in range(len(bounds) - 1):
        if k:
            t, r = _shared_block(cfg, shared, k - 1, h, x0, positions,
                                 attend)
            rows.append(r)
        if bounds[k + 1] > bounds[k]:
            h, mc = run(h, t, mc, bounds[k], bounds[k + 1])
    with jax.named_scope("attn"), jax.named_scope("kv_write"):
        rows = {n: jnp.stack([r[n] for r in rows]) for n in kv}
        if prefill:
            new_kv = {n: lax.dynamic_update_slice_in_dim(a, rows[n], 0,
                                                         axis=2)
                      for n, a in kv.items()}
        else:
            new_kv = _kv_write(kv, rows, length)
    return h, {"mamba": mc, "attn": new_kv}


# ---------------------------------------------------------------------------
# step factories (pure; jit/sharding applied by the launch layer)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, remat: str = "none"):
    def step(params, tokens, labels, extra_embeds=None):
        return loss_fn(params, cfg, tokens, labels,
                       extra_embeds=extra_embeds, remat=remat)
    return step


def make_prefill_step(cfg: ModelConfig):
    def step(params, tokens, cache, extra_embeds=None):
        return prefill(params, cfg, tokens, cache, extra_embeds=extra_embeds)
    return step


def make_decode_step(cfg: ModelConfig):
    def step(params, token, state):
        return decode_step(params, cfg, token, state)
    return step
