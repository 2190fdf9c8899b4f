"""Serving steps resolved through the (collective, strategy) registry.

The inference counterpart of ``launch/steps.py``'s train-step table: each
hosting flavor is one ``@register_impl("serve_step", ...)`` cell —

  replicated   every chip holds full weights; prefill/decode are plain
               jits (the single-host baseline, and the only hosting the
               hybrid family supports — its replicated shared blocks
               and per-application cache are not in the flat cached
               layer scan).
  lane_zero3   1/p weight hosting: the family's BlockSpec splits the
               params exactly like training (models/blockstack.py), the
               (L, B, p, s) masters stay sharded, and every prefill/
               decode re-gathers layer-by-layer through
               ``comm.prefetch_allgather`` with the one-layer prefetch
               (``scan_stack_cached``) — a pod can serve checkpoints it
               cannot hold replicated.  Decode SLOTS are sharded over
               the global rank (lane-major, matching ``scatter``), the
               batch-1 prefill runs replicated, and the fresh cache is
               distributed into its slot through the ``kv_splice``
               collective (decomposed lane bcast + local splice).

A :class:`ServeStep` is hosting-agnostic to its caller (the engine):
``prepare`` lays the weights out, ``init_state``/``prefill``/``decode``/
``splice`` are the four jitted entry points, and ``collectives`` names
the registry cells the step resolves (what the conformance grid and the
api-surface lock assert).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import CommConfig, LaneComm, get_impl, has_impl, \
    register_impl, strategies_for
from repro.configs.base import ModelConfig
from repro.core import LaneTopology
from repro.models import decode_step, init_cache, prefill
from repro.models.blockstack import (
    ShardedStack, block_stack_spec, resolve_extras_prefetch_blocks,
    resolve_prefetch_blocks, shard_stack,
    split_params,
)
from repro.models.layers import _dtype
from repro.models.transformer import ServeState, _SCANNED_FAMILIES

__all__ = ["ServeContext", "ServeStep", "build_serve_step",
           "serve_hostings", "load_serve_params"]


@dataclasses.dataclass(frozen=True)
class ServeContext:
    """Everything a registered serve-step builder needs.

    slots: decode batch width (lane_zero3: must divide by the mesh's
    chip count — each chip owns a contiguous global-rank block of
    slots).  prefetch_blocks: the ZeRO-3 gather pipeline B (0 =
    cost-model auto, -1 = blocking negative control), mirroring
    ``run.fsdp_prefetch``.  kv_strategy: which registered ``kv_splice``
    cell distributes a fresh prefill into its slot.
    """
    cfg: ModelConfig
    max_seq: int
    slots: int
    mesh: Any = None
    prefetch_blocks: int = 0
    kv_strategy: str = "lane"
    # tensor-parallel degree for the hosted forwards: the mesh's "model"
    # axis (must equal this size) runs the MLP activation allgathers
    # through model-axis (collective, strategy) cells; 1 = off.  zero3
    # hosting only — it composes the 1/p weight gather (batch axes) with
    # the TP activation collectives (model axis), the serving face of
    # the 3D pods × data × model mesh
    model_parallel: int = 1


@dataclasses.dataclass
class ServeStep:
    """One hosting flavor's jitted serving surface (see module docstring).

    prepare(params) -> hosted          lay the replicated tree out
    init_state() -> ServeState         batched (slots) zero state
    prefill(hosted, toks(1,b), true_len, extra=None)
        -> (logits (1,1,V) at the last TRUE position, batch-1 state)
    decode(hosted, tok(slots,1), state) -> (logits (slots,1,V), state)
    splice(state, state1, slot) -> state   write the batch-1 state into
        global slot ``slot`` (a traced int32 array — one compile serves
        every slot)
    collectives: {"weights": (collective, strategy), "kv": ...} — the
        registry cells this step resolves (empty for replicated).
    debug_lower: optional ``(params) -> {name: compiled-HLO text}`` AOT
        hook — lowers the step's jitted surfaces WITHOUT executing them,
        for the lanelint step sweep (``repro.analysis``).  None when the
        hosting has no distributed lowering worth walking (replicated).
    """
    hosting: str
    cfg: ModelConfig
    ctx: ServeContext
    prepare: Callable
    init_state: Callable
    prefill: Callable
    decode: Callable
    splice: Callable
    collectives: dict
    debug_lower: Any = None


def serve_hostings() -> tuple:
    """Registered serve_step hostings, in registration order (the derived
    table benches/tests enumerate)."""
    return strategies_for("serve_step")


def build_serve_step(cfg: ModelConfig, *, max_seq: int, slots: int,
                     hosting: str = "replicated", mesh=None,
                     prefetch_blocks: int = 0,
                     kv_strategy: str = "lane",
                     model_parallel: int = 1) -> ServeStep:
    """Resolve ``hosting`` from the serve_step registry and build."""
    if not has_impl("serve_step", hosting):
        raise ValueError(
            f"unknown serving hosting {hosting!r}; registered: "
            f"{serve_hostings()}")
    if model_parallel > 1 and hosting != "lane_zero3":
        raise ValueError(
            f"model_parallel > 1 needs hosting='lane_zero3' (got "
            f"{hosting!r}); replicated hosting has no mesh to carry the "
            f"'model' axis")
    ctx = ServeContext(cfg=cfg, max_seq=max_seq, slots=slots, mesh=mesh,
                       prefetch_blocks=prefetch_blocks,
                       kv_strategy=kv_strategy,
                       model_parallel=model_parallel)
    return get_impl("serve_step", hosting).fn(ctx)


def _init_serve_state(cfg: ModelConfig, batch: int, max_seq: int):
    """Zero ServeState at the model compute dtype (audio gets a zero
    batched enc_kv buffer the per-request splice fills)."""
    dt = _dtype(cfg)
    cache = init_cache(cfg, batch, max_seq, dtype=dt)
    enc_kv = None
    if cfg.family == "audio":
        K, hd = cfg.num_kv_heads, cfg.hd()
        shape = (cfg.num_layers, batch, cfg.encoder_seq, K, hd)
        enc_kv = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    return ServeState(cache=cache, length=jnp.zeros((batch,), jnp.int32),
                      enc_kv=enc_kv)


# every stacked cache leaf — kv (L,B,S,K,hd), the stacked mamba states,
# the hybrid per-application kv (applications,B,S,K,hd), enc_kv (L,B,Te,K,hd) — keeps
# batch at axis 1; length is the single axis-0 exception
_BATCH_AXIS = 1


def _splice_leaf(big, small, slot, axis=_BATCH_AXIS):
    return lax.dynamic_update_slice_in_dim(
        big, small.astype(big.dtype), jnp.asarray(slot, jnp.int32),
        axis=axis)


# ---------------------------------------------------------------------------
# replicated hosting
# ---------------------------------------------------------------------------

@register_impl("serve_step", "replicated", auto_ok=False)
def _serve_replicated(ctx: ServeContext) -> ServeStep:
    cfg = ctx.cfg

    @jax.jit
    def _init():
        return _init_serve_state(cfg, ctx.slots, ctx.max_seq)

    @jax.jit
    def _prefill(params, toks, true_len, extra=None):
        cache1 = init_cache(cfg, 1, ctx.max_seq, dtype=_dtype(cfg))
        return prefill(params, cfg, toks, cache1, extra_embeds=extra,
                       true_len=true_len)

    @partial(jax.jit, donate_argnums=(2,))
    def _decode(params, tok, state):
        return decode_step(params, cfg, tok, state)

    @partial(jax.jit, donate_argnums=(0,))
    def _splice(state, st1, slot):
        cache = jax.tree.map(lambda b, s: _splice_leaf(b, s, slot),
                             state.cache, st1.cache)
        length = lax.dynamic_update_slice(
            state.length, st1.length.astype(state.length.dtype),
            (jnp.asarray(slot, jnp.int32),))
        enc_kv = state.enc_kv
        if state.enc_kv is not None:
            enc_kv = jax.tree.map(lambda b, s: _splice_leaf(b, s, slot),
                                  state.enc_kv, st1.enc_kv)
        return ServeState(cache=cache, length=length, enc_kv=enc_kv)

    return ServeStep(hosting="replicated", cfg=cfg, ctx=ctx,
                     prepare=lambda params: params, init_state=_init,
                     prefill=_prefill, decode=_decode, splice=_splice,
                     collectives={})


# ---------------------------------------------------------------------------
# lane_zero3 hosting (1/p weights, sharded slots)
# ---------------------------------------------------------------------------

@register_impl("serve_step", "lane_zero3", auto_ok=False)
def _serve_zero3(ctx: ServeContext) -> ServeStep:
    from repro.launch.mesh import batch_axes
    from repro.launch.steps import zero3_stack_layouts
    cfg = ctx.cfg
    if ctx.mesh is None:
        raise ValueError("lane_zero3 serving needs a mesh (slots and "
                         "weights are sharded over it)")
    if cfg.family == "hybrid":
        raise ValueError(
            "the hybrid family cannot serve from 1/p-sharded weights "
            "(its replicated shared blocks and per-application cache "
            "are not in the flat cached layer scan); use "
            "hosting='replicated'")
    mesh = ctx.mesh
    ba = batch_axes(mesh)
    topo = LaneTopology(node_axes=ba[1:], lane_axis=ba[0])
    n, N = topo.sizes(mesh)
    p = max(n * N, 1)
    if ctx.slots % p:
        raise ValueError(
            f"slots={ctx.slots} must be divisible by the chip count "
            f"p={p} (each chip owns a contiguous global-rank block)")
    lays = zero3_stack_layouts(cfg)
    lay_b, lay_e = lays["blocks"], lays["extras"]
    Bb = resolve_prefetch_blocks(lay_b.row_elems, n, N, ctx.prefetch_blocks)
    Be = resolve_extras_prefetch_blocks(lay_e.row_elems, n, N,
                                        ctx.prefetch_blocks)
    blocking = ctx.prefetch_blocks == -1
    ccfg = CommConfig(prefetch_blocks=ctx.prefetch_blocks)
    weights_cell = ("prefetch_allgather",
                    "blocking" if blocking else "lane_pipelined")
    tp = max(ctx.model_parallel, 1)
    if tp > 1:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get("model", 1) != tp:
            raise ValueError(
                f"model_parallel={tp} needs a mesh 'model' axis of that "
                f"size (mesh axes: {sizes})")

    # slot ownership follows the GLOBAL rank (lane-major, the scatter /
    # kv_splice block order); the weight masters keep the training
    # placement (shard_stack's node-major stripe order)
    bpart = (topo.lane_axis, *topo.node_axes)
    master = P(None, None, (*topo.node_axes, topo.lane_axis), None)
    fspec = block_stack_spec(cfg)

    def _comm():
        return LaneComm(topo, ccfg)

    def _assemble(hosted, comm):
        """Sharded masters + replicated leftovers -> the params tree the
        cached forwards consume (extras gathered ONCE per call — no
        backward here, so no vjp bookkeeping)."""
        shards_b = hosted["blocks"].reshape(lay_b.length, -1)
        shards_e = hosted["extras"].reshape(-1)
        params = {k: v for k, v in hosted.items()
                  if k not in ("blocks", "extras")}
        params.update(lay_e.unflatten_row(
            comm.prefetch_allgather(shards_e, num_blocks=Be)))
        params["blocks"] = ShardedStack(
            shards_b,
            lambda x: lay_b.unflatten_row(
                comm.prefetch_allgather(x, num_blocks=Bb)),
            prefetch=not blocking)
        return params

    def prepare(params):
        """Replicated init_model tree -> sharded host masters, placed."""
        stack, extras, repl = split_params(fspec, params)
        shards_b, got_b = shard_stack(stack, n, N, ctx.prefetch_blocks)
        shards_e, got_e = shard_stack(extras, n, N, ctx.prefetch_blocks,
                                      stacked=False)
        if (got_b, got_e) != (Bb, Be):
            raise RuntimeError(
                f"prepare resolved prefetch blocks {(got_b, got_e)} but "
                f"the step was built for {(Bb, Be)}")
        hosted = {k: jax.device_put(v, NamedSharding(mesh, P()))
                  for k, v in repl.items()}
        hosted["blocks"] = jax.device_put(shards_b,
                                          NamedSharding(mesh, master))
        hosted["extras"] = jax.device_put(shards_e,
                                          NamedSharding(mesh, master))
        return hosted

    def _hspec(hosted):
        spec = {k: jax.tree.map(lambda _: P(), v)
                for k, v in hosted.items() if k not in ("blocks", "extras")}
        spec["blocks"] = spec["extras"] = master
        return spec

    def _sspec(state: ServeState):
        """Slot-sharded PartitionSpec tree of a batched ServeState."""
        leaf = lambda a: P(None, bpart, *([None] * (a.ndim - 2)))
        return ServeState(
            cache=jax.tree.map(leaf, state.cache),
            length=P(bpart),
            enc_kv=None if state.enc_kv is None
            else jax.tree.map(leaf, state.enc_kv))

    state_t = jax.eval_shape(
        lambda: _init_serve_state(cfg, ctx.slots, ctx.max_seq))
    sspec = _sspec(state_t)
    repl_spec = jax.tree.map(lambda _: P(), state_t)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                            is_leaf=lambda x: isinstance(x, P))

    def init_state():
        state = jax.jit(
            lambda: _init_serve_state(cfg, ctx.slots, ctx.max_seq),
            out_shardings=state_sh)()
        return state

    hspec_cache: dict = {}

    def _wrap(fn, in_specs, out_specs, donate=()):
        sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm, donate_argnums=donate)

    def _get(kind, hosted, build):
        key = kind
        if key not in hspec_cache:
            hspec_cache[key] = build(_hspec(hosted))
        return hspec_cache[key]

    def _pctx():
        """parallel_context of the hosted forwards: the TP activation
        collectives trace inside it (no-op context when tp == 1).  The
        TP forward is bitwise replicated over "model" (mlp_tp gathers
        its outputs full), so the replicated out_specs stay sound."""
        import contextlib
        if tp == 1:
            return contextlib.nullcontext()
        from repro.models.parallel import parallel_context
        return parallel_context(tp=tp, tp_comm=LaneComm(
            LaneTopology(node_axes=(), lane_axis="model"), ccfg))

    def _prefill_local(hosted, toks, true_len, extra):
        comm = _comm()
        params = _assemble(hosted, comm)
        cache1 = init_cache(cfg, 1, ctx.max_seq, dtype=_dtype(cfg))
        with _pctx():
            return prefill(params, cfg, toks, cache1, extra_embeds=extra,
                           true_len=true_len)

    def prefill_step(hosted, toks, true_len, extra=None):
        # batch-1 prefill runs REPLICATED (every chip computes the same
        # gathered-weight forward — deterministic, so out_specs P() is
        # sound); the splice below distributes the result to its slot
        if extra is None:
            fn = _get("prefill", hosted, lambda hs: _wrap(
                lambda h, t, l: _prefill_local(h, t, l, None),
                (hs, P(), P()), (P(), repl_spec)))
            return fn(hosted, toks, jnp.asarray(true_len, jnp.int32))
        fn = _get("prefill_extra", hosted, lambda hs: _wrap(
            _prefill_local, (hs, P(), P(), P()), (P(), repl_spec)))
        return fn(hosted, toks, jnp.asarray(true_len, jnp.int32), extra)

    def _decode_local(hosted, tok, state):
        comm = _comm()
        params = _assemble(hosted, comm)
        with _pctx():
            return decode_step(params, cfg, tok, state)

    def _decode_fn(hosted):
        return _get("decode", hosted, lambda hs: _wrap(
            _decode_local, (hs, P(bpart, None), sspec),
            (P(bpart, None, None), sspec), donate=(2,)))

    def decode(hosted, tok, state):
        return _decode_fn(hosted)(hosted, tok, state)

    def _splice_local(state, st1, slot):
        comm = _comm()
        sp = lambda axis: (lambda big, small: comm.kv_splice(
            big, small=small, slot=slot, batch_axis=axis,
            strategy=ctx.kv_strategy))
        cache = jax.tree.map(sp(_BATCH_AXIS), state.cache, st1.cache)
        length = comm.kv_splice(state.length, small=st1.length, slot=slot,
                                batch_axis=0, strategy=ctx.kv_strategy)
        enc_kv = state.enc_kv
        if state.enc_kv is not None:
            enc_kv = jax.tree.map(sp(_BATCH_AXIS), state.enc_kv,
                                  st1.enc_kv)
        return ServeState(cache=cache, length=length, enc_kv=enc_kv)

    splice_fn = _wrap(_splice_local, (sspec, repl_spec, P()), sspec,
                      donate=(0,))

    def splice(state, st1, slot):
        return splice_fn(state, st1, jnp.asarray(slot, jnp.int32))

    def debug_lower(params):
        """AOT compiled HLO of the distributed serving surfaces (decode
        with its prefetch weight gathers, and the kv_splice) — nothing
        executes; the lanelint step sweep walks the text for R1."""
        hosted = prepare(params)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), hosted)
        tok = jax.ShapeDtypeStruct((ctx.slots, 1), jnp.int32)
        st_t = jax.eval_shape(
            lambda: _init_serve_state(cfg, ctx.slots, ctx.max_seq))
        st1_t = jax.eval_shape(
            lambda: _init_serve_state(cfg, 1, ctx.max_seq))
        slot = jax.ShapeDtypeStruct((), jnp.int32)
        dec = _decode_fn(hosted).lower(shapes, tok,
                                       st_t).compile().as_text()
        spl = splice_fn.lower(st_t, st1_t, slot).compile().as_text()
        return {"decode": dec, "splice": spl}

    cells = {"weights": weights_cell,
             "kv": ("kv_splice", ctx.kv_strategy)}
    if tp > 1:
        cells["tp"] = ("allgather", "auto")
    return ServeStep(
        hosting="lane_zero3", cfg=cfg, ctx=ctx, prepare=prepare,
        init_state=init_state, prefill=prefill_step, decode=decode,
        splice=splice, collectives=cells,
        debug_lower=debug_lower)


# ---------------------------------------------------------------------------
# checkpoint -> serving weights (the PR-5 cross-layout canonical path)
# ---------------------------------------------------------------------------

def load_serve_params(ckpt_dir: str, cfg: ModelConfig,
                      step: Optional[int] = None):
    """Replicated serving weights from ANY training checkpoint layout.

    Reads the canonical flat order (crc-verified), pairs it against the
    stored layout's state template, lifts to the replicated form through
    the same ``state_to_replicated`` path training restarts use, drops
    the optimizer state, and casts back to the model's parameter dtypes.
    A zero3 ServeStep re-shards the result through ``prepare`` — so a
    checkpoint written at p chips serves at any p′.  Returns
    ``(params, step)``.
    """
    from repro.checkpoint import load_canonical
    from repro.launch.steps import _abs_params, _canonical_state_template, \
        state_to_replicated
    man, arrays, got = load_canonical(ckpt_dir, step)
    entry = (man.get("layout") or {})
    params_t = _abs_params(cfg)
    state_t = _canonical_state_template(cfg, entry)
    n_state = len(jax.tree.leaves(state_t))
    n_params = len(jax.tree.leaves(params_t))
    if len(arrays) == n_state:
        state = jax.tree.unflatten(jax.tree.structure(state_t), arrays)
        params, _ = state_to_replicated(cfg, entry, state)
    elif len(arrays) == n_params \
            and entry.get("kind", "replicated") == "replicated":
        params = jax.tree.unflatten(jax.tree.structure(params_t), arrays)
    else:
        raise ValueError(
            f"checkpoint at {ckpt_dir} holds {len(arrays)} leaves; a "
            f"{entry.get('kind', 'replicated')!r} state of this model "
            f"has {n_state} (or {n_params} params-only) — different "
            f"model?")
    params = jax.tree.map(lambda v, t: jnp.asarray(v).astype(t.dtype),
                          params, params_t)
    return params, got
