"""Step builders: train (GSPMD baseline + lane-decomposed variant), serve.

`build_train_step`   — jit/GSPMD end-to-end: the "native library" baseline.
                       Optional microbatch gradient accumulation (memory
                       control at 4k×256) — grads accumulate in fp32.
`build_train_step_lane` — the paper's technique as a first-class backend:
                       shard_map manual over the batch axes (pod, data),
                       GSPMD auto over "model"; all collectives run
                       through a repro.comm.LaneComm, and the per-strategy
                       step CONSTRUCTION dispatches through the same
                       registry (@register_impl("train_step", ...) below)
                       — no strategy if-chains.  Params replicated over
                       batch axes in the non-ZeRO flavors (≤ ~10B models).
`build_prefill_step` / `build_decode_step` — serving.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import (CommConfig, LaneComm, get_impl, register_impl,
                        register_param_layout)
from repro.configs.base import ModelConfig, RunConfig
from repro.core import LaneTopology
from repro.models import init_model, loss_fn, prefill, decode_step
from repro.models.blockstack import (
    ShardedStack, StackLayout, block_stack_spec,
    resolve_extras_prefetch_blocks, resolve_prefetch_blocks,
    shard_stack, split_params, stack_layout,
)
from repro.models.parallel import parallel_context
from repro.models.transformer import ShardedBlocks  # noqa: F401 (re-export)
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.adamw import global_norm
from repro.optim.gradsync import (
    _unflatten_bucket, _flatten_bucket, decay_mask_flat, resolve_num_buckets,
    zero1_param_shard, zero1_unshard, zero3_param_shard,
)
from .mesh import batch_axes


# ---------------------------------------------------------------------------
# GSPMD baseline train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, run: RunConfig,
                     opt: AdamWConfig, batch_axes: tuple[str, ...] = (),
                     accum_dtype=None):
    """(params, opt_state, tokens, labels[, extra]) → (loss, params, opt).

    accum_dtype: microbatch gradient-accumulation precision (None =
    ``run.accum_dtype``).  bf16 halves the accumulator's HBM residency
    (the fp32 buffer is ~2 GB/chip for dbrx); stochastic error stays
    below the int8-DCN compression bound already accepted for the
    lane_int8 strategy.
    """
    if accum_dtype is None:
        accum_dtype = _accum_dtype(run)

    def lf(p, tok, lab, ex):
        return loss_fn(p, cfg, tok, lab, extra_embeds=ex, remat=run.remat)

    def step(params, opt_state, tokens, labels, extra=None):
        mb = max(run.microbatch, 1)
        if mb == 1:
            loss, grads = jax.value_and_grad(lf)(params, tokens, labels, extra)
        else:
            B = tokens.shape[0]
            if B % mb:
                raise ValueError(
                    f"per-chip batch {B} must divide by microbatch {mb}")

            def sh(a):
                if a is None:
                    return None
                a = a.reshape(mb, B // mb, *a.shape[1:])
                if batch_axes:
                    # the (B,)→(mb, B/mb) reshape is ambiguous to GSPMD's
                    # propagation; without this constraint the per-µstep
                    # slice keeps the FULL local batch (verified: 16×
                    # activation memory on llama3.2 train_4k)
                    a = jax.lax.with_sharding_constraint(
                        a, P(None, batch_axes, *([None] * (a.ndim - 2))))
                return a

            tokens_mb, labels_mb = sh(tokens), sh(labels)
            extra_mb = sh(extra)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)

            def acc(carry, xs):
                lsum, g = carry
                tok, lab = xs[0], xs[1]
                ex = xs[2] if len(xs) == 3 else None
                l, gi = jax.value_and_grad(lf)(params, tok, lab, ex)
                g = jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), g, gi)
                return (lsum + l, g), None

            xs = ((tokens_mb, labels_mb) if extra is None
                  else (tokens_mb, labels_mb, extra_mb))
            (lsum, gsum), _ = jax.lax.scan(acc, (0.0, g0), xs)
            loss = lsum / mb
            grads = jax.tree.map(lambda g: (g / mb), gsum)
        new_params, new_opt = adamw_update(opt, grads, opt_state, params)
        return loss, new_params, new_opt

    return step


# ---------------------------------------------------------------------------
# lane-decomposed train step (the paper's technique, swappable)
# ---------------------------------------------------------------------------
#
# Per-strategy step CONSTRUCTION dispatches through the repro.comm
# registry too: each flavor is one @register_impl("train_step", ...)
# below, so a new gradsync variant is a registration here plus its
# grad_sync impl in repro/comm/impls.py — never an if-chain edit.  The
# builder contract: fn(comm: LaneComm, ctx: StepContext) -> step where
# step(params, opt_state, tokens, labels, extra=None) -> (loss, params,
# opt_state), traced inside shard_map with ctx.ba manual.

@dataclasses.dataclass(frozen=True)
class StepContext:
    """Everything a registered train-step builder needs besides the comm."""
    cfg: ModelConfig
    run: RunConfig
    opt: AdamWConfig
    mesh: object
    ba: tuple
    single: bool                   # one batch axis: no distinct lane level


def build_train_step_lane(cfg: ModelConfig, run: RunConfig, opt: AdamWConfig,
                          mesh, param_specs, *, tuner=None):
    """Manual over batch axes; collectives via repro.comm.LaneComm.

    The step flavor is resolved from the train_step registry by
    ``run.gradsync`` (valid names: ``repro.comm.strategies_for
    ("train_step")`` — native/lane/lane_pipelined/lane_int8/auto share
    the replicated-parameter step, lane_zero1/lane_zero3 build the
    sharded-optimizer steps; see the registrations below).  All lane
    strategies bucket the flat gradient vector (K = run.gradsync_buckets
    via CommConfig.from_run, 0 = cost-model auto) so the DCN lane hop of
    one bucket overlaps the ICI node collective of the next (§5
    pipeline); ``"auto"`` lets the cost model pick the sync strategy per
    payload and records the choice on the returned comm's ``selections``.
    On a single-batch-axis mesh the node level is trivial and every
    replicated flavor degrades to the native one-shot psum.
    ``param_specs`` is accepted so existing call sites keep working,
    but unused: the caller owns the shard_map in/out specs of the returned step.
    ``tuner`` (a ``repro.tuning.Tuner`` or None) lands on the comm's
    ``CommConfig.tuner``: measured timing-cache costs then outrank the
    closed-form model in every auto dispatch this step makes.

    Returns ``(step, comm)``: the comm carries the topology
    (``comm.topo``), the recorded auto ``Selection``s, and the
    ``param_layout`` answer the driver keys its master state / shard
    specs / checkpoint layout off (see ``init_lane_train_state``).
    """
    ba = batch_axes(mesh)
    single = len(ba) == 1
    # single-axis meshes get an empty node level (n = 1): the lane axis
    # IS the communicator, matching the paper's N-node/1-per-node corner
    topo = LaneTopology(node_axes=ba[1:], lane_axis=ba[0])
    ccfg = CommConfig.from_run(run)
    if tuner is not None:
        ccfg = dataclasses.replace(ccfg, tuner=tuner)
    comm = LaneComm(topo, ccfg, mesh=mesh)
    ctx = StepContext(cfg, run, opt, mesh, ba, single)
    builder = get_impl("train_step", run.gradsync)
    return builder.fn(comm, ctx), comm


def _parallel_kwargs(ctx: StepContext, comm: LaneComm) -> dict:
    """The static :func:`repro.models.parallel.parallel_context` kwargs of
    this run's third-axis configuration (empty dict = no TP and no EP —
    the zero-overhead default path).

    TP rides a DEGENERATE n=1 decomposition over the mesh's "model" axis
    (the lane axis IS the whole communicator) so the activation
    allgathers resolve through the same (collective, strategy) cells —
    and the same tuner — as every other lowering; EP routes through the
    BATCH-axes communicator ``comm`` itself (every chip is an expert
    owner), so the ``moe_route`` alltoalls share its auto/tuned config.
    """
    run = ctx.run
    pc: dict = {}
    tp = run.model_parallel
    if tp > 1:
        sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
        if sizes.get("model", 1) != tp:
            raise ValueError(
                f"model_parallel={tp} needs a mesh 'model' axis of that "
                f"size (mesh axes: {sizes})")
        tp_comm = LaneComm(LaneTopology(node_axes=(), lane_axis="model"),
                           comm.cfg, mesh=ctx.mesh)
        # expose the model-axis comm for selection introspection (the
        # driver reports comm.selections; TP records on its own comm)
        comm.tp_comm = tp_comm
        pc.update(tp=tp, tp_comm=tp_comm)
    if run.expert_parallel:
        E = ctx.cfg.num_experts
        psz = 1
        for a in ctx.ba:
            psz *= dict(zip(ctx.mesh.axis_names,
                            ctx.mesh.devices.shape))[a]
        if E % max(psz, 1):
            raise ValueError(
                f"expert_parallel needs num_experts={E} divisible by the "
                f"batch-axes chip count p={psz}")
        pc.update(ep=True, ep_comm=comm, ep_blocks=run.ep_blocks)
    return pc


def _make_loss(ctx: StepContext, comm: Optional[LaneComm] = None):
    """The traced loss closure; with a comm and an active third axis it
    enters the :func:`parallel_context` around the forward trace (the
    backward operates on the traced jaxpr, so trace-time routing is all
    the context must cover).  A ``p["ep_experts"]`` entry — the zero3
    step's differentiated local expert tree — is popped off the params
    and carried on the context for the scan body to slice per layer."""
    pc = _parallel_kwargs(ctx, comm) if comm is not None else {}

    def lf(p, tok, lab, ex):
        if not pc:
            return loss_fn(p, ctx.cfg, tok, lab, extra_embeds=ex,
                           remat=ctx.run.remat)
        p = dict(p)
        experts = p.pop("ep_experts", None)
        with parallel_context(**pc, ep_experts=experts):
            return loss_fn(p, ctx.cfg, tok, lab, extra_embeds=ex,
                           remat=ctx.run.remat)
    return lf


# which leaves the tensor-parallel MLP partitions: exactly the weights
# models/layers.mlp_tp computes as zero-padded column blocks per model
# rank (everything else stays bitwise replicated over "model" thanks to
# its custom VJP gathering the input cotangent full)
_TP_LEAF_KEYS = ("w_up", "w_gate", "w_down")


def _is_tp_leaf(keys) -> bool:
    return "mlp" in keys and bool(keys) and keys[-1] in _TP_LEAF_KEYS


def _tp_assemble_tree(grads):
    """Assemble the TP MLP weight grads over the "model" axis.

    Each model rank's grad is the zero-padded column block of exactly its
    slice of the replicated gradient (mlp_tp's custom VJP), so ONE psum
    concatenates disjoint blocks — adding zeros is exact, which is what
    keeps the TP==replicated step pin bitwise.  Non-MLP leaves are
    already bitwise replicated over "model" and pass through untouched.
    """
    import jax.tree_util as jtu

    def fix(path, g):
        keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
        return jax.lax.psum(g, "model") if _is_tp_leaf(keys) else g
    return jtu.tree_map_with_path(fix, grads)


def _tp_row_mask(stack_t, lay) -> jnp.ndarray:
    """Per-element 0/1 fp32 mask over one UNPADDED flat stack row: 1
    exactly on the TP-partitioned MLP weight elements.  Leaf order
    matches :class:`StackLayout` (both use the default tree flatten)."""
    import jax.tree_util as jtu
    flat, _ = jtu.tree_flatten_with_path(stack_t)
    if len(flat) != len(lay.metas):
        raise ValueError(
            f"stack template has {len(flat)} leaves but the layout "
            f"records {len(lay.metas)} — layout drift")
    parts = []
    for (path, _), (shape, _) in zip(flat, lay.metas):
        keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
        parts.append(jnp.full((math.prod(shape),),
                              1.0 if _is_tp_leaf(keys) else 0.0,
                              jnp.float32))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)


# the (L, E, ...) expert FFN weights the expert-parallel zero3 master
# keeps OUT of the gathered flat stack; the router stays in the stack
# (its grad is dense over tokens, and every chip routes locally)
_EXPERT_KEYS = ("w_up", "w_gate", "w_down")


def split_expert_stack(stack: dict):
    """Split a MoE layer stack into (stack_without_experts, experts).

    ``experts`` holds the moe FFN weight leaves in their NATURAL
    (L, E, ...) shapes — the expert-parallel master shards them over E
    across the batch-axes chips (global-rank order) and never gathers
    them; the returned stack keeps the router (and everything else) for
    the ordinary flat 1/p layout.
    """
    if "moe" not in stack:
        raise ValueError(
            f"expert_parallel needs a 'moe' stack entry (stack keys: "
            f"{sorted(stack)})")
    moe = stack["moe"]
    experts = {k: moe[k] for k in _EXPERT_KEYS if k in moe}
    if not experts:
        raise ValueError("'moe' stack entry has no expert FFN weights")
    rest = {k: v for k, v in moe.items() if k not in experts}
    return {**stack, "moe": rest}, experts


def _register_replicated(strategy: str):
    register_param_layout(strategy, "replicated")

    @register_impl("train_step", strategy, auto_ok=False)
    def _build(comm, ctx, _strategy=strategy):
        """Replicated-parameter step: full grad sync + tree AdamW."""
        lf = _make_loss(ctx, comm)
        eff = "native" if ctx.single else _strategy
        tp_on = ctx.run.model_parallel > 1
        vg = _microbatched(
            lambda p, t, l, e: jax.value_and_grad(lf)(p, t, l, e),
            ctx.run.microbatch, _accum_dtype(ctx.run))

        def step(params, opt_state, tokens, labels, extra=None):
            loss, grads = vg(params, tokens, labels, extra)
            loss = jax.lax.pmean(loss, ctx.ba)
            if tp_on:
                grads = _tp_assemble_tree(grads)
            grads = comm.grad_sync(grads, strategy=eff)
            new_params, new_opt = adamw_update(ctx.opt, grads, opt_state,
                                               params)
            return loss, new_params, new_opt
        return step
    return _build


for _s in ("native", "lane", "lane_pipelined", "lane_int8", "auto"):
    _register_replicated(_s)


register_param_layout("lane_quorum", "replicated")


@register_impl("train_step", "lane_quorum", auto_ok=False)
def _build_quorum(comm, ctx: StepContext):
    """Quorum-degraded replicated step: the DEGRADED rung of the ladder.

    Same replicated-parameter step as ``lane``, but it takes a trailing
    ``quorum_mask`` argument — the watchdog's 0/1 float32 vector over
    the lane (pod) axis, replicated (P() spec) so each pod dynamically
    indexes its own bit — and routes gradients through the
    ``lane_quorum`` grad-sync: masked pods contribute zero and the mean
    rescales by the live count (runtime.straggler.quorum_stage).  The
    logged loss degrades the same way (node pmean, then quorum_mean
    over the lane).  With ``quorum_mask=None`` (or all ones) the step
    is the full-quorum path, bit-identical to ``lane`` on power-of-two
    pod counts.  The driver keys the 6-argument shard_map signature off
    ``step.needs_quorum_mask``.
    """
    from repro.runtime.straggler import quorum_mean
    lf = _make_loss(ctx)
    topo = comm.topo
    vg = _microbatched(
        lambda p, t, l, e: jax.value_and_grad(lf)(p, t, l, e),
        ctx.run.microbatch, _accum_dtype(ctx.run))

    def step(params, opt_state, tokens, labels, extra=None,
             quorum_mask=None):
        loss, grads = vg(params, tokens, labels, extra)
        if quorum_mask is None:
            c = jnp.ones((), jnp.float32)
            loss = jax.lax.pmean(loss, ctx.ba)
        else:
            c = jnp.asarray(quorum_mask,
                            jnp.float32)[topo.lane_rank()]
            if topo.node_axes:
                loss = jax.lax.pmean(loss, topo.node_axes)
            loss = quorum_mean(loss, topo.lane_axis, c)
        grads = comm.grad_sync(grads, strategy="lane_quorum",
                               contributing=c)
        new_params, new_opt = adamw_update(ctx.opt, grads, opt_state,
                                           params)
        return loss, new_params, new_opt
    step.needs_quorum_mask = True
    return step


register_param_layout("lane_zero1", "zero1")


@register_impl("train_step", "lane_zero1", auto_ok=False)
def _build_zero1(comm, ctx: StepContext):
    """ZeRO-1 step: data-sharded flat grads + moments through the
    optimizer; the paper's trailing AllGather moves PAST the update
    (same bytes, applied to fresh params, moments stay sharded).  The
    shard layout is bucket-major, so param sharding/unsharding goes
    through gradsync.zero1_param_shard / zero1_unshard with the same K.
    Optimizer semantics match the unsharded adamw_update exactly: the
    TRUE global grad norm is one extra scalar psum over the shard norms
    and weight decay follows the per-element matrices-only mask."""
    if ctx.single:
        return get_impl("train_step", "native").fn(comm, ctx)
    lf = _make_loss(ctx, comm)
    topo, opt, run = comm.topo, ctx.opt, ctx.run
    vg = _microbatched(
        lambda p, t, l, e: jax.value_and_grad(lf)(p, t, l, e),
        run.microbatch, _accum_dtype(run))

    def step(params, opt_state, tokens, labels, extra=None):
        loss, grads = vg(params, tokens, labels, extra)
        loss = jax.lax.pmean(loss, ctx.ba)
        total = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
        K = resolve_num_buckets(total, topo.n(), run.gradsync_buckets)
        shard_flat, spec = comm.grad_sync(grads, strategy="lane_zero1",
                                          num_buckets=K)
        pflat, pspec = _flatten_bucket(params, pad_to=K * topo.n())
        mine = zero1_param_shard(pflat, topo, K)
        dmask = zero1_param_shard(
            decay_mask_flat(params, pad_to=K * topo.n()), topo, K)
        # true global grad norm: shards are disjoint over the node level
        # and lane-replicated, so ONE scalar psum over the node axes sums
        # the per-shard square norms to the full-tree norm (padding
        # contributes zeros)
        gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(jnp.square(shard_flat)),
                                      topo.node_axes))
        scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))
        # sharded moments: opt_state here is the *sharded* flat state
        newp_shard, new_opt = _adamw_flat(opt, shard_flat, opt_state, mine,
                                          scale=scale, decay_mask=dmask)
        full = zero1_unshard(newp_shard, topo, K)
        new_params = _unflatten_bucket(full, pspec)
        return loss, new_params, new_opt
    return step


register_param_layout("lane_zero3", "zero3")


@register_impl("train_step", "lane_zero3", auto_ok=False)
def _build_zero3(comm, ctx: StepContext):
    """ZeRO-3/FSDP step, family-agnostic: the family's registered
    BlockSpec (models/blockstack.py) splits the params into the scanned
    layer stack, the "extras" pseudo-layer (embed/final_norm/...) and the
    replicated leftovers (only the hybrid's shared blocks, adapters and
    projections).  The stack stays sharded 1/p per chip (shard_stack
    layout) and is re-gathered LAYER BY LAYER inside the forward scan via
    comm.prefetch_allgather — the pipelined AG(lane)→AG(node) with a
    one-layer prefetch buffer so layer i+1's gather overlaps layer i's
    compute (run.fsdp_prefetch: 0 = cost-model block count, >0 =
    override, -1 = blocking negative control); the extras shard gathers
    ONCE per step through the same pipeline.  run.fsdp_regather=True
    re-runs each layer's gather in the backward under remat so backward
    residuals stay 1/p too (see ShardedStack).  Gradients for both
    sharded trees need no separate sync: the gathers' AD transposes ARE
    the lane_zero3 reduce-scatters; only the replicated leftovers (when
    any) sync through the bucketed lane path.  Optimizer semantics match
    native: one scalar psum over the (lane × node) shard norms recovers
    the true global grad norm for clipping, and the flat decay masks
    reproduce matrices-only weight decay."""
    ba, run, opt, cfg = ctx.ba, ctx.run, ctx.opt, ctx.cfg
    if len(ba) < 2:
        # zero3 shards over the (lane × node) product and its gather
        # pipeline needs the two levels to be DISTINCT axes; there is no
        # sensible single-axis degradation (unlike the other strategies,
        # which fall back to native)
        raise ValueError(
            "lane_zero3 needs distinct lane and node batch axes (a "
            "multi-pod mesh); use native or lane_zero1 on single-"
            f"batch-axis meshes (got batch axes {ba})")
    topo = comm.topo
    lf = _make_loss(ctx, comm)
    ep_on = run.expert_parallel
    tp_on = run.model_parallel > 1
    n_, N_ = topo.sizes(ctx.mesh)
    p_ = max(n_ * N_, 1)
    layouts = zero3_stack_layouts(cfg, ep=ep_on)
    lay_b, lay_e = layouts["blocks"], layouts["extras"]
    # abstract stack template (same leaf order as lay_b): the TP row mask
    # and the EP expert-dtype template both key off it
    fspec3 = block_stack_spec(cfg)
    stack_t, _, _ = split_params(fspec3, _abs_params(cfg))
    exp_t = None
    if ep_on:
        stack_t, exp_t = split_expert_stack(stack_t)
    mask_row = _tp_row_mask(stack_t, lay_b) if tp_on else None
    Bb = resolve_prefetch_blocks(lay_b.row_elems, n_, N_, run.fsdp_prefetch)
    # extras (vocab·d embed + head) resolves from its OWN row payload —
    # a positive override tuned for the layer stack is not inherited
    Be = resolve_extras_prefetch_blocks(lay_e.row_elems, n_, N_,
                                        run.fsdp_prefetch)
    blocking = run.fsdp_prefetch == -1
    if blocking and run.fsdp_regather:
        raise ValueError(
            "fsdp_prefetch=-1 (the blocking negative control) and "
            "fsdp_regather are mutually exclusive: the re-gather scan "
            "would silently replace the blocking lowering the control "
            "is supposed to measure")

    def gather_layer(x):
        return lay_b.unflatten_row(comm.prefetch_allgather(x, num_blocks=Bb))

    def gather_extras(x):
        return lay_e.unflatten_row(comm.prefetch_allgather(x, num_blocks=Be))

    def step(params, opt_state, tokens, labels, extra=None):
        """lane_zero3 train step.

        params["blocks"] / params["extras"] are this chip's shards — any
        shape reshapeable to (L, B·s) / (B·s,), e.g. the local blocks of
        the host-side (L, B, n·N, s) layouts from shard_stack; every
        other entry is replicated (the family spec's replicated_keys).
        opt_state is the split {"rest", "blocks", "extras"} state of
        zero3_opt_init.  The returned params keep both shards SHARDED
        (same shapes as the input): ZeRO-3 never materializes full layer
        parameters outside the per-layer prefetch window (the extras
        pseudo-layer stays live for the step — the "+1 layer" of the
        memory model).
        """
        bshape = params["blocks"].shape
        eshape = params["extras"].shape
        shards_b = params["blocks"].reshape(lay_b.length, -1)
        shards_e = params["extras"].reshape(-1)
        experts = params["experts"] if ep_on else {}
        repl = {k: v for k, v in params.items()
                if k not in ("blocks", "extras", "experts")}
        have_repl = bool(jax.tree.leaves(repl))

        # the extras pseudo-layer gathers ONCE per step, OUTSIDE the
        # microbatch scan (with microbatching the naive in-loss gather
        # would re-gather the vocab·d payload per µbatch); the explicit
        # vjp keeps the AD transpose — applying it to the accumulated
        # cotangent below IS the extras reduce-scatter
        extras_tree, extras_vjp = jax.vjp(gather_extras, shards_e)

        def vg(repl_p, sh_b, ext, exp, tok, lab, ex):
            def lf3(repl_p, sh_b, ext, exp):
                p = dict(repl_p)
                p.update(ext)
                p["blocks"] = ShardedStack(sh_b, gather_layer,
                                           prefetch=not blocking,
                                           regather=run.fsdp_regather)
                if ep_on:
                    # fp32 master -> model dtype inside the trace, the
                    # same cast point as the gather path's unflatten_row
                    p["ep_experts"] = jax.tree.map(
                        lambda a, t: a.astype(t.dtype), exp, exp_t)
                return lf(p, tok, lab, ex)
            return jax.value_and_grad(lf3, argnums=(0, 1, 2, 3))(
                repl_p, sh_b, ext, exp)

        vg = _microbatched(vg, run.microbatch, _accum_dtype(run))
        loss, (g_repl, g_b, g_ext, g_exp) = vg(repl, shards_b, extras_tree,
                                               experts, tokens, labels,
                                               extra)
        (g_e,) = extras_vjp(jax.tree.map(
            lambda g, t: g.astype(t.dtype), g_ext, extras_tree))
        loss = jax.lax.pmean(loss, ba)
        # the gathers' transposes already reduce-scattered g_b/g_e over
        # (lane × node) — sum over replicas; only the mean is left.  The
        # EP expert grads arrive COMPLETE on the owner the same way (the
        # routing alltoall's transpose returns every chip's cotangent to
        # the expert's home), so they too only need the replica mean
        nrep = _axprod(ba)
        g_b, g_e = g_b / nrep, g_e / nrep
        if ep_on:
            g_exp = jax.tree.map(lambda g: g / nrep, g_exp)
        if tp_on:
            # each model rank's flat stripe holds the zero-padded column
            # block of the TP-partitioned MLP leaves (mlp_tp's custom
            # VJP); one masked psum over "model" assembles them exactly
            # (adding zeros is bit-exact) and leaves every other element
            # — already bitwise replicated over "model" — untouched
            row = mask_row
            pad = shards_b.shape[1] * p_ - row.shape[0]
            if pad:
                row = jnp.concatenate(
                    [row, jnp.zeros((pad,), jnp.float32)])
            m = jnp.tile(zero3_param_shard(row, topo, Bb), lay_b.length)
            gb = g_b.reshape(-1)
            g_b = (gb * (1 - m)
                   + jax.lax.psum(gb * m, "model")).reshape(g_b.shape)
            if have_repl:
                g_repl = _tp_assemble_tree(g_repl)
        if have_repl:
            g_repl = comm.grad_sync(g_repl, strategy="lane")
        # true global grad norm over stack + extras + experts +
        # leftovers: the 1/p stripes (and the E/p expert slices) are
        # disjoint, so one scalar psum over BOTH levels totals their
        # square norms; g_repl is fully reduced (replicated), added once
        loc_sq = jnp.sum(jnp.square(g_b)) + jnp.sum(jnp.square(g_e))
        if ep_on:
            loc_sq = loc_sq + sum(
                jnp.sum(jnp.square(g)) for g in jax.tree.leaves(g_exp))
        gsq = jax.lax.psum(loc_sq, (topo.lane_axis, *topo.node_axes))
        if have_repl:
            gsq = gsq + global_norm(g_repl) ** 2
        gnorm = jnp.sqrt(gsq)
        scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gnorm, 1e-9))
        new_repl, new_opt_rest = adamw_update(
            opt, g_repl, opt_state["rest"], repl, grad_norm=gnorm)
        dmask_b = jnp.tile(
            zero3_param_shard(lay_b.decay_mask(shards_b.shape[1] * p_),
                              topo, Bb),
            lay_b.length)
        ob = opt_state["blocks"]
        newp_b, nob = _adamw_flat(
            opt, g_b.reshape(-1),
            {"m": ob["m"].reshape(-1), "v": ob["v"].reshape(-1),
             "count": ob["count"]},
            shards_b.reshape(-1), scale=scale, decay_mask=dmask_b)
        dmask_e = zero3_param_shard(
            lay_e.decay_mask(shards_e.shape[0] * p_), topo, Be)
        oe = opt_state["extras"]
        newp_e, noe = _adamw_flat(
            opt, g_e, {"m": oe["m"].reshape(-1), "v": oe["v"].reshape(-1),
                       "count": oe["count"]},
            shards_e, scale=scale, decay_mask=dmask_e)
        new_params = dict(new_repl)
        new_params["blocks"] = newp_b.reshape(bshape)
        new_params["extras"] = newp_e.reshape(eshape)
        new_opt = {"rest": new_opt_rest,
                   "blocks": {"m": nob["m"].reshape(ob["m"].shape),
                              "v": nob["v"].reshape(ob["v"].shape),
                              "count": nob["count"]},
                   "extras": {"m": noe["m"].reshape(oe["m"].shape),
                              "v": noe["v"].reshape(oe["v"].shape),
                              "count": noe["count"]}}
        if ep_on:
            # the (L, E/p, ...) local expert master updates in place —
            # same elementwise AdamW math as the flat shards, natural
            # shapes (every FFN leaf decays: ndim >= 2, matching the
            # gather layout's per-element decay mask)
            new_exp, new_opt_exp = adamw_update(
                opt, g_exp, opt_state["experts"], experts,
                grad_norm=gnorm)
            new_params["experts"] = new_exp
            new_opt["experts"] = new_opt_exp
        return loss, new_params, new_opt
    return step


def _axprod(axes):
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def _accum_dtype(run: RunConfig):
    return jnp.bfloat16 if run.accum_dtype == "bfloat16" else jnp.float32


def _microbatched(vg_fn, mb: int, accum_dtype):
    """Microbatch gradient accumulation for the lane step builders.

    Wraps a value-and-grad callable ``vg(*diff_args, tokens, labels,
    extra) -> (loss, grads)`` (``grads`` mirroring the differentiated
    args) into a version with the identical signature that splits the
    LOCAL batch (this is inside shard_map — the leading dim is already
    the per-chip shard) into ``mb`` µbatches scanned sequentially.
    Gradients accumulate in ``accum_dtype``: fp32 is parity-exact with
    the unaccumulated step up to summation order; bf16 halves the
    accumulator's HBM residency (the same error class already accepted
    for the lane_int8 DCN hop).  ``mb <= 1`` returns ``vg_fn`` unchanged
    — zero overhead on the default path.
    """
    if mb <= 1:
        return vg_fn

    def wrapped(*args):
        *diff, tokens, labels, extra = args
        B = tokens.shape[0]
        if B % mb:
            raise ValueError(
                f"local batch {B} not divisible by microbatch={mb} "
                f"(pick a global batch divisible by devices × microbatch)")
        sh = lambda a: None if a is None else \
            a.reshape(mb, B // mb, *a.shape[1:])
        toks, labs, ex = sh(tokens), sh(labels), sh(extra)
        # grads structure comes from the wrapped fn itself (a single tree
        # or a tuple, depending on argnums) — eval_shape, never traced in
        _, g_shape = jax.eval_shape(
            vg_fn, *diff, toks[0], labs[0], None if ex is None else ex[0])
        g0 = jax.tree.map(lambda s: jnp.zeros(s.shape, accum_dtype),
                          g_shape)

        def acc(carry, xs):
            lsum, g = carry
            t, l = xs[0], xs[1]
            e = xs[2] if len(xs) == 3 else None
            li, gi = vg_fn(*diff, t, l, e)
            g = jax.tree.map(lambda a, b: a + b.astype(accum_dtype), g, gi)
            return (lsum + li, g), None

        xs = (toks, labs) if ex is None else (toks, labs, ex)
        (lsum, gsum), _ = jax.lax.scan(acc, (jnp.zeros((), jnp.float32), g0),
                                       xs)
        return lsum / mb, jax.tree.map(lambda g: g / mb, gsum)
    return wrapped


def _adamw_flat(opt: AdamWConfig, g, state, p, *, scale=None,
                decay_mask=None):
    """AdamW on a flat fp32 shard (ZeRO-1 / ZeRO-3).

    scale: global-norm clip factor — the CALLER computes it from the true
    global norm (one extra scalar psum over shard norms) so every shard
    clips by the same full-model scale, exactly like adamw_update; None
    skips clipping.  decay_mask: 0/1 per-element mask of the leaves
    adamw_update would decay (matrices; see gradsync.decay_mask_flat);
    None decays every element uniformly (legacy behavior, kept for bare
    callers)."""
    from repro.optim.adamw import cosine_lr
    count = state["count"] + 1
    lr = cosine_lr(opt, count)
    if scale is not None:
        g = g * scale
    m = opt.b1 * state["m"] + (1 - opt.b1) * g
    v = opt.b2 * state["v"] + (1 - opt.b2) * jnp.square(g)
    c1 = 1 - opt.b1 ** count.astype(jnp.float32)
    c2 = 1 - opt.b2 ** count.astype(jnp.float32)
    decay = opt.weight_decay * p
    if decay_mask is not None:
        decay = decay * decay_mask
    step = (m / c1) / (jnp.sqrt(v / c2) + opt.eps) + decay
    return p - lr * step, {"m": m, "v": v, "count": count}


def zero1_opt_init(params, topo_n: int, num_buckets: int = 0):
    """Flat sharded fp32 optimizer state for the lane_zero1 path.

    Pass ``run.gradsync_buckets`` as num_buckets: the shard size depends
    on the bucketed padding (K·n), so this MUST match the train step's
    override — resolve_num_buckets is deterministic, so the default 0
    (auto) agrees with the step's auto choice, but a nonzero override on
    one side only produces a shape mismatch inside the jitted step.
    """
    total = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    K = resolve_num_buckets(total, topo_n, num_buckets)
    padded = -(-total // (K * topo_n)) * (K * topo_n)
    sz = padded // topo_n
    return {"m": jnp.zeros((sz,), jnp.float32),
            "v": jnp.zeros((sz,), jnp.float32),
            "count": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# ZeRO-3 stack sharding (the lane_zero3 / FSDP path)
# ---------------------------------------------------------------------------
#
# The family's scanned layer stack (every leaf (L, ...)) is flattened per
# layer into an (L, D) fp32 master copy, padded to D_pad = B·n·N·s, and
# each chip keeps the (L, B·s) stripe of the gradsync.zero3_param_shard
# layout; the non-stack, non-replicated params (embed/final_norm/...)
# become the "extras" pseudo-layer — one more (1, Be, n·N, se) master.
# The host-side arrays are shaped (L, B, n·N, s) so a plain NamedSharding
# P(None, None, (*node_axes, lane_axis), None) places exactly stripe
# (node_rank·N + lane_rank) on each chip — no host-side rank arithmetic.
# The layout machinery itself is family-agnostic and lives in
# repro.models.blockstack (StackLayout / shard_stack /
# resolve_prefetch_blocks, re-exported here); everything both sides of
# the shard_map boundary must agree on derives deterministically from
# the ModelConfig via zero3_stack_layouts.

def zero3_stack_layouts(cfg: ModelConfig, ep: bool = False) -> dict:
    """``{"blocks": StackLayout, "extras": StackLayout}`` of the family's
    sharded stacks (derived via eval_shape — never materializes
    weights).  ``blocks`` is the (L, ...) scanned stack; ``extras`` is
    the single pseudo-layer of everything else except the family spec's
    replicated keys.  ``ep=True`` (expert parallelism) keeps the MoE
    expert FFN leaves OUT of the blocks layout — they live in the
    never-gathered (L, E/p, ...) local expert master instead."""
    fspec = block_stack_spec(cfg)
    abs_params = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg))
    stack, extras, _ = split_params(fspec, abs_params)
    if ep:
        stack, _ = split_expert_stack(stack)
    return {"blocks": stack_layout(stack, stacked=True),
            "extras": stack_layout(extras, stacked=False)}


def zero3_opt_init(cfg: ModelConfig, params, n: int, N: int,
                   fsdp_prefetch: int = 0, ep: bool = False):
    """Split optimizer state for the lane_zero3 step: flat sharded fp32
    moments in the (L, B, p, s) master layouts for the layer stack AND
    the extras pseudo-layer, ordinary AdamW tree state for the family's
    replicated keys (empty for most families; the hybrid's shared blocks,
    adapters and projections).  The B resolution MUST match the step's
    (resolve_prefetch_blocks is deterministic, so the default 0 agrees;
    pass the same run.fsdp_prefetch override on both sides).  ``ep=True``
    adds the "experts" entry: natural-shape fp32 moments for the expert
    master (host-side FULL (L, E, ...) — the driver's NamedSharding
    places the E/p slice per chip exactly like the params master)."""
    fspec = block_stack_spec(cfg)
    stack, extras, repl = split_params(fspec, params)
    experts = None
    if ep:
        stack, experts = split_expert_stack(stack)
    # derive the moment shapes FROM shard_stack (via eval_shape, no
    # weight materialization) so the layout invariant lives in one place
    sh_b = jax.eval_shape(
        lambda b: shard_stack(b, n, N, fsdp_prefetch)[0], stack)
    sh_e = jax.eval_shape(
        lambda e: shard_stack(e, n, N, fsdp_prefetch, stacked=False)[0],
        extras)
    flat_state = lambda s: {"m": jnp.zeros(s.shape, jnp.float32),
                            "v": jnp.zeros(s.shape, jnp.float32),
                            "count": jnp.zeros((), jnp.int32)}
    out = {"rest": adamw_init(repl), "blocks": flat_state(sh_b),
           "extras": flat_state(sh_e)}
    if ep:
        out["experts"] = adamw_init(experts)
    return out


# ---------------------------------------------------------------------------
# driver-side master state: layout-aware init + shard specs + ckpt layout
# ---------------------------------------------------------------------------
#
# Everything the training driver must agree on with the jitted step —
# which master layout the params/optimizer state live in, the shard_map
# in/out PartitionSpecs of that layout, and the checkpoint layout that
# canonicalizes it — is derived HERE from the same LaneComm.param_layout
# answer the step builders register, so a new strategy's driver wiring is
# its register_param_layout(...) line, not a fourth if-chain.

@dataclasses.dataclass
class LaneTrainState:
    """Host-side master state for one lane train-step flavor.

    params/opt_state: host (global-view) arrays in the step's master
        layout — device_put against ``to_shardings(mesh)`` before use.
    pspecs/ospecs: the matching shard_map in/out PartitionSpec trees.
    ckpt_layout: the repro.checkpoint layout that canonicalizes this
        state on disk (thread into AsyncCheckpointer/restore_checkpoint).
    """
    params: object
    opt_state: object
    pspecs: object
    ospecs: object
    ckpt_layout: object

    def to_shardings(self, mesh):
        from jax.sharding import NamedSharding
        mk = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return mk(self.pspecs), mk(self.ospecs)


def zero1_checkpoint_layout(params, n: int, num_buckets: int = 0):
    """Checkpoint layout of the lane_zero1 flat optimizer moments (the
    SAME K/padding resolution as zero1_opt_init and the train step)."""
    from repro.checkpoint import Zero1CheckpointLayout
    total = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    K = resolve_num_buckets(total, n, num_buckets)
    return Zero1CheckpointLayout(total, K, n)


def zero3_checkpoint_layout(cfg: ModelConfig, n: int, N: int,
                            fsdp_prefetch: int = 0, ep: bool = False):
    """Checkpoint layout of the lane_zero3 (L, B, p, s) masters — the
    layer stack AND the extras pseudo-layer (the SAME B resolution as
    shard_stack / zero3_opt_init / the step).  ``ep=True`` records the
    expert-parallel flavor: the blocks geometry excludes the expert FFN
    leaves (they checkpoint in their natural (L, E, ...) shapes, which
    ARE canonical — identity passthrough)."""
    from repro.checkpoint import Zero3CheckpointLayout
    layouts = zero3_stack_layouts(cfg, ep=ep)
    lay_b, lay_e = layouts["blocks"], layouts["extras"]
    Bb = resolve_prefetch_blocks(lay_b.row_elems, n, N, fsdp_prefetch)
    Be = resolve_extras_prefetch_blocks(lay_e.row_elems, n, N,
                                        fsdp_prefetch)
    return Zero3CheckpointLayout(lay_b.length, lay_b.row_elems, Bb,
                                 max(n * N, 1),
                                 extra_elems=lay_e.row_elems,
                                 extra_blocks=Be, ep=ep)


def init_lane_train_state(cfg: ModelConfig, run: RunConfig, mesh,
                          params, comm: LaneComm = None) -> LaneTrainState:
    """Master state + specs + checkpoint layout for ``run.gradsync``.

    ``params`` is the replicated init_model tree; the ZeRO flavors
    re-lay it out host-side (blockstack.shard_stack) and build fresh sharded
    optimizer state.  Pass the ``comm`` returned by
    ``build_train_step_lane`` so the layout/topology decision is read off
    the SAME object the step was built against (None re-derives it from
    the mesh — identical by construction, for callers without a step).
    """
    from repro.checkpoint import REPLICATED
    if comm is None:
        ba = batch_axes(mesh)
        topo = LaneTopology(node_axes=ba[1:], lane_axis=ba[0])
        comm = LaneComm(topo, CommConfig.from_run(run), mesh=mesh)
    topo = comm.topo
    kind = comm.param_layout(run.gradsync)
    n, N = topo.sizes(mesh)
    pspecs = jax.tree.map(lambda _: P(), params)
    if kind == "replicated":
        opt = adamw_init(params)
        return LaneTrainState(params, opt, pspecs,
                              jax.tree.map(lambda _: P(), opt), REPLICATED)
    if kind == "zero1":
        layout = zero1_checkpoint_layout(params, n, run.gradsync_buckets)
        opt = {"m": jnp.zeros((layout.padded,), jnp.float32),
               "v": jnp.zeros((layout.padded,), jnp.float32),
               "count": jnp.zeros((), jnp.int32)}
        ospecs = {"m": P(topo.node_axes), "v": P(topo.node_axes),
                  "count": P()}
        return LaneTrainState(params, opt, pspecs, ospecs, layout)
    if kind != "zero3":
        raise ValueError(f"unknown lane state layout kind {kind!r}")
    ep_on = run.expert_parallel
    fspec = block_stack_spec(cfg)
    stack, extras, repl = split_params(fspec, params)
    experts = None
    if ep_on:
        stack, experts = split_expert_stack(stack)
    shards_b, Bb = shard_stack(stack, n, N, run.fsdp_prefetch)
    shards_e, Be = shard_stack(extras, n, N, run.fsdp_prefetch,
                               stacked=False)
    layout = zero3_checkpoint_layout(cfg, n, N, run.fsdp_prefetch,
                                     ep=ep_on)
    if tuple(shards_b.shape) != layout.master_shape \
            or Bb != layout.num_blocks \
            or tuple(shards_e.shape) != layout.extra_master_shape \
            or Be != layout.extra_blocks:
        # both sides derive B/padding from the stack element counts; if
        # the real trees and zero3_stack_layouts ever disagree the
        # checkpoint would silently record the wrong geometry
        raise ValueError(
            f"zero3 master layout drift: sharded stacks "
            f"{shards_b.shape}/{shards_e.shape} (B={Bb}/{Be}) vs "
            f"checkpoint layout {layout.master_shape}/"
            f"{layout.extra_master_shape} "
            f"(B={layout.num_blocks}/{layout.extra_blocks})")
    p3 = dict(repl)
    p3["blocks"] = shards_b
    p3["extras"] = shards_e
    if ep_on:
        # fp32 expert master in NATURAL (L, E, ...) shapes; the E-dim
        # sharding below places exactly experts [r·E/p, (r+1)·E/p) on
        # global rank r = lane_rank·n + node_rank — the owner order
        # moe_block_ep routes by
        p3["experts"] = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), experts)
    opt = zero3_opt_init(cfg, params, n, N, run.fsdp_prefetch, ep=ep_on)
    master_spec = P(None, None, (*topo.node_axes, topo.lane_axis), None)
    pspecs = jax.tree.map(lambda _: P(), p3)
    pspecs["blocks"] = pspecs["extras"] = master_spec
    ospecs = jax.tree.map(lambda _: P(), opt)
    ospecs["blocks"]["m"] = ospecs["blocks"]["v"] = master_spec
    ospecs["extras"]["m"] = ospecs["extras"]["v"] = master_spec
    if ep_on:
        expert_spec = P(None, (topo.lane_axis, *topo.node_axes))
        exp_specs = jax.tree.map(lambda _: expert_spec, experts)
        pspecs["experts"] = exp_specs
        ospecs["experts"]["m"] = exp_specs
        ospecs["experts"]["v"] = exp_specs
    return LaneTrainState(p3, opt, pspecs, ospecs, layout)


# ---------------------------------------------------------------------------
# cross-layout restore (zero3 <-> zero1 <-> replicated, via canonical order)
# ---------------------------------------------------------------------------
#
# Every checkpoint layout canonicalizes to the SAME underlying element
# order (the unpadded flat parameter order — see the flat-order
# primitives in repro.checkpoint.layouts), so a checkpoint written under
# one strategy layout restores into another: lift the stored canonical
# leaves to the replicated (params, adamw) form, then re-lay them out
# through the destination layout exactly like init_lane_train_state lays
# out a fresh init.  Pure reshape/transpose end to end; the only value
# change is the dtype cast when a fp32 ZeRO master restores into a
# sub-fp32 replicated parameter (and back).  Geometry that genuinely
# differs — a different model — still raises.

def _abs_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))


def _abs_adamw(params_t):
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {"m": jax.tree.map(f32, params_t),
            "v": jax.tree.map(f32, params_t),
            "count": jax.ShapeDtypeStruct((), jnp.int32)}


def _canonical_state_template(cfg: ModelConfig, entry: dict):
    """Abstract (params, opt_state) tree whose leaves have the CANONICAL
    shapes a checkpoint of layout ``entry`` stores — the pairing target
    for repro.checkpoint.load_canonical's raw arrays."""
    kind = (entry or {}).get("kind", "replicated")
    params_t = _abs_params(cfg)
    if kind == "replicated":
        return params_t, _abs_adamw(params_t)
    f32 = lambda shape: jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
    count_t = jax.ShapeDtypeStruct((), jnp.int32)
    if kind == "zero1":
        total = int(entry.get("total_elems", 0))
        return params_t, {"m": f32((total,)), "v": f32((total,)),
                         "count": count_t}
    if kind != "zero3":
        raise ValueError(f"unknown checkpoint layout kind {kind!r}")
    if not entry.get("extra_elems"):
        raise ValueError(
            "zero3 checkpoint predates the extras pseudo-layer (no "
            "extra_elems in its layout entry); cross-layout restore "
            "needs the current master format")
    fspec = block_stack_spec(cfg)
    stack_t, extras_t, repl_t = split_params(fspec, params_t)
    ep = bool(entry.get("ep"))
    exp_t = None
    if ep:
        stack_t, exp_t = split_expert_stack(stack_t)
    lay_b = stack_layout(stack_t, stacked=True)
    lay_e = stack_layout(extras_t, stacked=False)
    flat_t = lambda lay: {"m": f32((lay.length, lay.row_elems)),
                          "v": f32((lay.length, lay.row_elems)),
                          "count": count_t}
    p_t = dict(repl_t)
    p_t["blocks"] = f32((lay_b.length, lay_b.row_elems))
    p_t["extras"] = f32((1, lay_e.row_elems))
    o_t = {"rest": _abs_adamw(repl_t), "blocks": flat_t(lay_b),
           "extras": flat_t(lay_e)}
    if ep:
        # the expert master checkpoints in its natural (L, E, ...) fp32
        # shapes — natural IS canonical for experts (identity layout)
        exp_f32 = jax.tree.map(lambda l: f32(l.shape), exp_t)
        p_t["experts"] = exp_f32
        o_t["experts"] = {"m": exp_f32, "v": exp_f32, "count": count_t}
    return p_t, o_t


def state_to_replicated(cfg: ModelConfig, entry: dict, state):
    """Canonical-form (params, opt_state) of layout ``entry`` -> the
    replicated (params tree, adamw tree) form.  Host-side plumbing: the
    flat-order split/unstack primitives only."""
    import numpy as np
    kind = (entry or {}).get("kind", "replicated")
    if kind == "replicated":
        return state
    params, opt = state
    params_t = _abs_params(cfg)
    if kind == "zero1":
        from repro.checkpoint import split_flat_order
        leaves_t = jax.tree.leaves(params_t)
        treedef = jax.tree.structure(params_t)
        mk = lambda flat: jax.tree.unflatten(
            treedef, split_flat_order(flat, [l.shape for l in leaves_t]))
        return params, {"m": mk(opt["m"]), "v": mk(opt["v"]),
                        "count": opt["count"]}
    if kind != "zero3":
        raise ValueError(f"unknown lane state layout kind {kind!r}")
    fspec = block_stack_spec(cfg)
    stack_t, extras_t, _ = split_params(fspec, params_t)
    ep = bool(entry.get("ep"))
    exp_t = None
    if ep:
        stack_t, exp_t = split_expert_stack(stack_t)
    lay_b = stack_layout(stack_t, stacked=True)
    lay_e = stack_layout(extras_t, stacked=False)
    p_repl = {k: v for k, v in params.items()
              if k not in ("blocks", "extras", "experts")}
    p_repl.update(lay_e.unflatten(np.asarray(params["extras"])))
    blocks = lay_b.unflatten(np.asarray(params["blocks"]))
    if ep:
        # fold the natural-shape expert master back into the stack's moe
        # subtree (cast to the model's parameter dtype, like unflatten)
        moe = dict(blocks.get("moe", {}))
        for k, v in params["experts"].items():
            moe[k] = np.asarray(v).astype(exp_t[k].dtype)
        blocks = {**blocks, "moe": moe}
    p_repl["blocks"] = blocks

    def moments(name):
        tree = {k: v for k, v in opt["rest"][name].items()}
        tree.update(lay_e.unflatten(np.asarray(opt["extras"][name]),
                                    dtype=np.float32))
        blk = lay_b.unflatten(np.asarray(opt["blocks"][name]),
                              dtype=np.float32)
        if ep:
            moe_m = dict(blk.get("moe", {}))
            for k, v in opt["experts"][name].items():
                moe_m[k] = np.asarray(v)
            blk = {**blk, "moe": moe_m}
        tree["blocks"] = blk
        return tree

    return p_repl, {"m": moments("m"), "v": moments("v"),
                    "count": opt["blocks"]["count"]}


def replicated_to_state(cfg: ModelConfig, run: RunConfig, n: int, N: int,
                        params, opt_state, *, kind: str):
    """Replicated (params, adamw) values -> the host master state of
    layout ``kind`` for the CURRENT (n, N) topology — the value-carrying
    twin of init_lane_train_state's layout path."""
    import numpy as np
    if kind == "replicated":
        # cast back into the model's parameter dtypes (a fp32 ZeRO
        # master restoring into a bf16 replicated run)
        params_t = _abs_params(cfg)
        params = jax.tree.map(
            lambda v, t: np.asarray(v).astype(t.dtype), params, params_t)
        return params, opt_state
    if kind == "zero1":
        import jax.tree_util as jtu
        from repro.checkpoint import concat_flat_order
        layout = zero1_checkpoint_layout(params, n, run.gradsync_buckets)
        lay1 = lambda tree: layout.from_canonical(
            (jtu.DictKey("m"),),
            concat_flat_order(jax.tree.leaves(tree)))
        return params, {"m": lay1(opt_state["m"]),
                        "v": lay1(opt_state["v"]),
                        "count": opt_state["count"]}
    if kind != "zero3":
        raise ValueError(f"unknown lane state layout kind {kind!r}")
    ep = run.expert_parallel
    fspec = block_stack_spec(cfg)
    stack, extras, repl = split_params(fspec, params)
    experts = None
    if ep:
        stack, experts = split_expert_stack(stack)
    shards_b, _ = shard_stack(stack, n, N, run.fsdp_prefetch)
    shards_e, _ = shard_stack(extras, n, N, run.fsdp_prefetch,
                              stacked=False)
    p3 = dict(repl)
    p3["blocks"] = np.asarray(shards_b)
    p3["extras"] = np.asarray(shards_e)
    if ep:
        p3["experts"] = jax.tree.map(
            lambda a: np.asarray(a, np.float32), experts)

    def flat_state(name):
        m_stack, m_extras, _ = split_params(fspec, opt_state[name])
        m_exp = None
        if ep:
            m_stack, m_exp = split_expert_stack(m_stack)
        return (np.asarray(shard_stack(m_stack, n, N,
                                       run.fsdp_prefetch)[0]),
                np.asarray(shard_stack(m_extras, n, N, run.fsdp_prefetch,
                                       stacked=False)[0]),
                m_exp)
    mb, me, mx = flat_state("m")
    vb, ve, vx = flat_state("v")
    count = opt_state["count"]
    _, _, m_repl = split_params(fspec, opt_state["m"])
    _, _, v_repl = split_params(fspec, opt_state["v"])
    o3 = {"rest": {"m": m_repl, "v": v_repl, "count": count},
          "blocks": {"m": mb, "v": vb, "count": count},
          "extras": {"m": me, "v": ve, "count": count}}
    if ep:
        asf32 = lambda t: jax.tree.map(
            lambda a: np.asarray(a, np.float32), t)
        o3["experts"] = {"m": asf32(mx), "v": asf32(vx), "count": count}
    return p3, o3


def restore_lane_train_state(ckpt_dir: str, cfg: ModelConfig,
                             run: RunConfig, mesh, st: LaneTrainState,
                             step: Optional[int] = None, shardings=None):
    """Restore a checkpoint into ``st``'s master layout, converting
    through the canonical replicated form when the checkpoint was
    written under a DIFFERENT strategy layout (e.g. a ``lane_zero3``
    checkpoint into a ``lane_zero1`` or replicated run, and back).
    Same-kind restores delegate to the ordinary layout-validated path.
    Returns ((params, opt_state), step); ``shardings`` (a
    ``st.to_shardings(mesh)`` pair) device_puts the result.

    Integrity: leaves crc-verify as they load.  With ``step=None`` a
    corrupt newest checkpoint falls back to the newest committed step
    that verifies (losing the steps since that commit, never the
    restart); an EXPLICIT step raises ``CheckpointCorruptError``.
    Geometry ValueErrors always propagate — a config mismatch must not
    be "survived" by resurrecting an older checkpoint."""
    import sys
    from repro.checkpoint import CheckpointCorruptError, committed_steps
    candidates = [step] if step is not None \
        else list(reversed(committed_steps(ckpt_dir)))
    if not candidates:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    last_err = None
    for cand in candidates:
        try:
            return _restore_lane_state_at(ckpt_dir, cfg, run, mesh, st,
                                          cand, shardings)
        except CheckpointCorruptError as e:
            last_err = e
            if step is not None:
                raise
            print(f"checkpoint step {cand} is corrupt ({e}); falling "
                  f"back to the previous committed step",
                  file=sys.stderr, flush=True)
    raise CheckpointCorruptError(
        f"no verifiable checkpoint in {ckpt_dir} "
        f"(tried steps {candidates})") from last_err


def _restore_lane_state_at(ckpt_dir: str, cfg: ModelConfig,
                           run: RunConfig, mesh, st: LaneTrainState,
                           step: int, shardings=None):
    from repro.checkpoint import load_canonical, restore_checkpoint
    from repro.checkpoint.store import peek_manifest
    # decide the kind from the manifest ALONE: the common same-kind
    # resume must not pay a second full read of multi-GB master arrays
    man, got = peek_manifest(ckpt_dir, step)
    entry = man.get("layout") or {}
    src_kind = entry.get("kind", "replicated")
    # the ep flag changes the zero3 master GEOMETRY (expert leaves leave
    # the flat stack): a same-kind/different-ep restore must go through
    # the canonical form, not the layout-validated fast path
    same_ep = bool(entry.get("ep", False)) == \
        bool(getattr(st.ckpt_layout, "ep", False))
    if src_kind == st.ckpt_layout.kind and same_ep:
        return restore_checkpoint(
            ckpt_dir, (st.params, st.opt_state), step=got,
            shardings=shardings, layout=st.ckpt_layout)
    _, arrays, got = load_canonical(ckpt_dir, got)
    src_t = _canonical_state_template(cfg, entry)
    refs = jax.tree.leaves(src_t)
    if len(refs) != len(arrays):
        raise ValueError(
            f"checkpoint holds {len(arrays)} leaves but a {src_kind!r} "
            f"state of this model has {len(refs)} (different model?)")
    for i, (ref, arr) in enumerate(zip(refs, arrays)):
        if tuple(ref.shape) != tuple(arr.shape):
            raise ValueError(
                f"cross-layout restore: canonical leaf {i} has shape "
                f"{tuple(arr.shape)} but a {src_kind!r} state of this "
                f"model stores {tuple(ref.shape)} (different model?)")
    src_state = jax.tree.unflatten(jax.tree.structure(src_t), arrays)
    repl_params, repl_opt = state_to_replicated(cfg, entry, src_state)
    ba = batch_axes(mesh)
    topo = LaneTopology(node_axes=ba[1:], lane_axis=ba[0])
    n, N = topo.sizes(mesh)
    params, opt = replicated_to_state(cfg, run, n, N, repl_params,
                                      repl_opt, kind=st.ckpt_layout.kind)
    if shardings is not None:
        params = jax.tree.map(jax.device_put, params, shardings[0])
        opt = jax.tree.map(jax.device_put, opt, shardings[1])
    return (params, opt), got


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------
# The serving RUNTIME lives in repro.serve.steps: hosting flavors are
# ("serve_step", strategy) registry cells exactly like the train-step
# table above, resolved through build_serve_step.  The two factories
# below are the unjitted lowering shims the dryrun HLO accountant uses
# (it applies its own shardings/donation and passes an external cache);
# they must stay semantically identical to the registry's "replicated"
# cell, which wraps the same model calls behind its own jit.

def build_serve_step(cfg: ModelConfig, **kw):
    """Registry-resolved serving step (see repro.serve.steps)."""
    from repro.serve.steps import build_serve_step as _build
    return _build(cfg, **kw)


def build_prefill_step(cfg: ModelConfig):
    def step(params, tokens, cache, extra=None):
        return prefill(params, cfg, tokens, cache, extra_embeds=extra)
    return step


def build_decode_step(cfg: ModelConfig):
    def step(params, token, state):
        return decode_step(params, cfg, token, state)
    return step
