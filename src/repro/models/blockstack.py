"""Family-agnostic ZeRO-3 sharded layer stack (the §5 recipe as a runtime).

The paper's §5 construction — pipeline a one-ported tree algorithm over a
payload split into blocks, lane level and node level structurally
concurrent — says nothing about what the payload *is*.  The first ZeRO-3
port nevertheless welded the machinery into ``models/transformer.py``
(``ShardedBlocks`` + ``_scan_blocks_prefetch``), so only the scanned
attention families could train with 1/p-sharded parameters; Mamba2,
hybrid and MoE configs silently fell back to replicated weights.

This module extracts the machinery into family-agnostic pieces:

  ``StackLayout``     the bucket-major 1/p flat layout of ONE stack of
                      parameters (the layer stack, or the embeddings/
                      final-norm "extras" treated as a single additional
                      layer) — flatten/unflatten, master-array shaping,
                      per-element decay mask.
  ``ShardedStack``    the traced stand-in for a sharded stack inside a
                      loss function: per-layer shard rows plus the gather
                      recipe; differentiable (the all-gather's AD
                      transpose IS the lane_zero3 reduce-scatter).
  ``scan_stack``      the layer scan: one-layer prefetch buffer (layer
                      i+1's gather structurally concurrent with layer i's
                      compute), a blocking negative control, and the
                      backward re-gather mode (the gather re-runs inside
                      a ``jax.checkpoint`` cell, so backward residuals
                      stay 1/p instead of L·D per chip).
  ``BlockSpec``       what a model family must declare to ride the stack:
                      which top-level param key is the scanned stack,
                      which keys stay replicated (the Zamba2 weight-shared
                      attention block), and how to build the per-layer
                      scan body.

Family specs register through the existing :mod:`repro.comm` registry
seam — ``@register_block_stack("ssm")`` is sugar for
``register_impl("block_stack", "ssm", ...)`` — so the set of lane-capable
families is one more derived table: the train-smoke sweep, the per-family
benchmark rows and the bench schema check all enumerate
``block_stack_families()`` instead of a hard-coded tuple.  The concrete
specs live in :mod:`repro.models.transformer` (the assembly layer that
owns the block bodies); the zero3 train step resolves them via
:func:`block_stack_spec`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.registry import get_impl, has_impl, register_impl, \
    strategies_for
from repro.core.costmodel import optimal_prefetch_blocks

__all__ = [
    "ShardedStack", "ShardedBlocks", "scan_stack", "scan_stack_cached",
    "StackLayout",
    "stack_layout", "shard_stack", "resolve_prefetch_blocks",
    "resolve_extras_prefetch_blocks", "BlockSpec",
    "register_block_stack", "block_stack_spec", "block_stack_families",
    "family_smoke_archs", "split_params",
]


# ---------------------------------------------------------------------------
# the traced stand-in + the prefetch scan
# ---------------------------------------------------------------------------

class ShardedStack:
    """Stand-in for a stacked parameter subtree when the stack is ZeRO-3
    sharded: each chip holds its 1/p stripe of every layer's flat weight
    vector plus the recipe to re-gather one layer on demand.

    shards   (L, B·s)-reshapeable array — this chip's per-layer stripe in
             the bucket-major ``zero3_param_shard`` layout.  Differentiable
             through the gather: the cotangent arriving on ``shards`` is
             the batch-summed, fully reduce-scattered layer gradient (the
             all-gather's transpose IS the lane_zero3 reduce-scatter).
    gather   shard row -> one layer's parameter tree (built by
             launch/steps.py around ``comm.prefetch_allgather`` + a
             ``StackLayout``).
    prefetch True: the layer scan carries a one-layer prefetch buffer —
             layer i+1's all-gather is issued in the same scan step as
             layer i's compute with no data dependence between them, so
             XLA may overlap gather and matmuls (verified structurally by
             ``launch.hlo_stats.collective_compute_concurrency``).
             False: blocking gather — each layer's compute consumes its
             own all-gather (the negative control).
    regather True: backward re-gather — each layer's gather runs INSIDE a
             ``jax.checkpoint`` cell together with the layer's compute, so
             the scan's backward residuals keep only (activations, 1/p
             shard row) per layer and the backward RE-RUNS the all-gather
             (the standard FSDP trick; pinned by an hlo_stats count —
             the backward HLO contains its own all-gathers).  Trades the
             forward's structural prefetch for 1/p backward memory:
             forward 1/p + 1 layer, backward 1/p + 1 layer.

    Not a pytree on purpose: it only ever exists *inside* a traced loss
    function (steps.py closes over gather and passes the shard array as
    the differentiated argument), so it must never cross a jit/grad
    boundary itself.
    """

    def __init__(self, shards, gather, *, prefetch: bool = True,
                 regather: bool = False):
        if regather and not prefetch:
            # the blocking mode exists as the prefetch proof's negative
            # control; silently lowering it as a remat'd re-gather scan
            # would invalidate the control measurement
            raise ValueError(
                "regather=True cannot be combined with prefetch=False (the "
                "blocking negative control); drop one of the two")
        self.shards = shards
        self.gather = gather
        self.prefetch = prefetch
        self.regather = regather


# the name the first ZeRO-3 port exported; same class, kept importable
ShardedBlocks = ShardedStack


def scan_stack(stack: ShardedStack, h, body):
    """Layer scan over ZeRO-3 shards with a one-layer prefetch buffer.

    ``body(h, layer_params, layer_idx) -> (h', aux)`` is the ordinary
    (possibly remat'd) block body; ``layer_idx`` is the traced scan index
    (the hybrid family conditions its weight-shared attention on it,
    everyone else ignores it), ``aux`` a scalar.  Returns
    ``(h, aux_ys (L,))``.

    Prefetch mode: the carry holds the *gathered* params of the layer
    about to run — step t gathers layer t+1's weights from its shard row
    while computing layer t from the carry; within a step the all-gather
    and the dots touch disjoint values, which is exactly the structural
    concurrency the §5 pipeline needs.  The scan covers layers 0..L-2
    (xs = shard rows 1..L-1); layer L-1 runs OUTSIDE the loop on the
    final carry, so exactly L gathers execute per forward — a wrapped xs
    would re-gather layer 0 on the last trip, and XLA cannot drop work
    from a single iteration of a while loop.

    Regather mode: the gather moves inside a ``jax.checkpoint`` cell with
    the body, so each layer is re-gathered in the backward (see
    :class:`ShardedStack`).  Blocking mode: each layer's compute consumes
    its own gather (the prefetch proof's negative control).
    """
    shards, gather = stack.shards, stack.gather
    L = shards.shape[0]
    idxs = jnp.arange(L)

    if stack.regather:
        # residuals per step: (h, shard row) — the gathered weights are
        # recomputed (re-gathered) by the checkpoint cell in the backward
        cell = jax.checkpoint(lambda hh, x, i: body(hh, gather(x), i))

        def step_regather(hh, xi):
            x, i = xi
            return cell(hh, x, i)
        return lax.scan(step_regather, h, (shards, idxs))

    if not stack.prefetch:
        # blocking: layer t's dots are data-dependent on layer t's gather
        def step_blocking(hh, xi):
            x, i = xi
            return body(hh, gather(x), i)
        return lax.scan(step_blocking, h, (shards, idxs))

    w0 = gather(shards[0])                  # layer 0: unavoidably blocking
    if L == 1:
        h, a = body(h, w0, idxs[0])
        return h, jnp.asarray(a)[None]

    def step(carry, xi):
        hh, w = carry
        x_next, i = xi
        w_next = gather(x_next)             # prefetch layer i+1 (no dep on w)
        hh, a = body(hh, w, i)              # compute layer i
        return (hh, w_next), a

    (h, w_last), aux_ys = lax.scan(step, (h, w0), (shards[1:], idxs[:-1]))
    h, a_last = body(h, w_last, idxs[-1])   # layer L-1: already gathered
    return h, jnp.concatenate([jnp.atleast_1d(aux_ys),
                               jnp.asarray(a_last)[None]])


def scan_stack_cached(stack: ShardedStack, h, xs, body):
    """The serving-side layer scan: :func:`scan_stack` with per-layer
    scanned INPUTS and stacked OUTPUTS (the KV/SSM cache rows).

    ``body(h, layer_params, xs_row) -> (h', ys_row)`` where ``xs`` and
    the returned ``ys`` are pytrees whose every leaf has a leading
    stack dim L (``xs_row``/``ys_row`` are single rows of them) — the
    cached prefill bodies thread (cache_in -> cache_out), and the audio
    prefill additionally emits the per-layer cross-attention K/V; the
    decode body takes its layer index from ``xs``, reads that layer of the
    cache it closes over, and emits only the layer's new cache rows, which
    the caller writes into the cache in place after the scan (so neither
    the stacked ``ys`` nor the concatenation with layer L-1's row is
    cache-sized).  No aux scalars, no regather (inference has no
    backward): just the same one-layer prefetch structure — layer i+1's
    all-gather issued alongside layer i's compute, layer L-1 outside the
    loop so exactly L gathers run.  Returns ``(h, ys)``.
    """
    shards, gather = stack.shards, stack.gather
    L = shards.shape[0]

    if not stack.prefetch:
        def step_blocking(hh, sx):
            srow, xrow = sx
            return body(hh, gather(srow), xrow)
        return lax.scan(step_blocking, h, (shards, xs))

    row = lambda t, i: jax.tree.map(lambda a: a[i], t)
    w0 = gather(shards[0])                  # layer 0: unavoidably blocking
    if L == 1:
        h, y = body(h, w0, row(xs, 0))
        return h, jax.tree.map(lambda a: a[None], y)

    def step(carry, sx):
        hh, w = carry
        s_next, xrow = sx
        w_next = gather(s_next)             # prefetch layer i+1 (no dep on w)
        hh, y = body(hh, w, xrow)           # compute layer i
        return (hh, w_next), y

    xs_head = jax.tree.map(lambda a: a[:-1], xs)
    (h, w_last), ys = lax.scan(step, (h, w0), (shards[1:], xs_head))
    h, y_last = body(h, w_last, row(xs, L - 1))  # layer L-1: gathered
    ys = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                      ys, y_last)
    return h, ys


# ---------------------------------------------------------------------------
# the bucket-major 1/p flat layout of one stack
# ---------------------------------------------------------------------------

class StackLayout:
    """Flat layout of ONE stack of parameters: ``length`` rows (layers),
    each the concatenation of its leaves' flat elements in tree order.

    ``stacked=True`` trees have a leading stack dim on every leaf (the
    scanned layer stack: metas are ``shape[1:]``); ``stacked=False``
    trees are a single pseudo-layer (the embeddings/final-norm "extras"
    stack: metas are the full shapes, length 1).  ``decay`` records, per
    leaf, whether ``adamw_update`` would weight-decay it (original
    ndim >= 2) — the flat per-element decay mask derives from it.

    Derived via ``eval_shape``-safe access (only ``.shape``/
    ``.dtype``/``.ndim`` of the leaves are read), so building a layout
    never materializes weights.
    """

    def __init__(self, metas, decay, treedef, row_elems: int, length: int,
                 stacked: bool):
        self.metas = metas              # ((row shape, dtype) per leaf)
        self.decay = decay              # (bool per leaf)
        self.treedef = treedef
        self.row_elems = row_elems      # D: unpadded flat size per row
        self.length = length            # L: rows in the stack
        self.stacked = stacked

    # names the first ZeRO-3 port used (the Zero3LayerSpec spelling)
    @property
    def layer_elems(self) -> int:
        return self.row_elems

    @property
    def num_layers(self) -> int:
        return self.length

    def unflatten_row(self, vec):
        """Padded flat fp32 row vector -> one row's parameter tree (leaves
        cast back to their stored dtypes)."""
        out, ofs = [], 0
        for shape, dtype in self.metas:
            sz = math.prod(shape)
            out.append(vec[ofs:ofs + sz].reshape(shape).astype(dtype))
            ofs += sz
        return jax.tree.unflatten(self.treedef, out)

    def flatten(self, tree, pad_to: int = 1):
        """The (L, D_pad) fp32 row matrix of ``tree`` (row-major per-leaf
        concatenation, zero-padded so D_pad % pad_to == 0)."""
        leaves = jax.tree.leaves(tree)
        L = self.length
        if self.stacked:
            flat = jnp.concatenate(
                [l.reshape(L, -1).astype(jnp.float32) for l in leaves],
                axis=1)
        else:
            flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32) for l in leaves])[None]
        pad = (-flat.shape[1]) % pad_to
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((L, pad), flat.dtype)], axis=1)
        return flat

    def unflatten(self, mat, dtype=None):
        """Inverse of :meth:`flatten` (host- or device-side): (L, >=D)
        row matrix -> the stacked tree, leaf dtypes restored (``dtype``
        overrides them — moment trees stay fp32)."""
        out, ofs = [], 0
        for shape, leaf_dtype in self.metas:
            sz = math.prod(shape)
            cols = mat[:, ofs:ofs + sz]
            if self.stacked:
                cols = cols.reshape(self.length, *shape)
            else:
                cols = cols.reshape(shape)
            out.append(cols.astype(dtype if dtype is not None
                                   else leaf_dtype))
            ofs += sz
        return jax.tree.unflatten(self.treedef, out)

    def decay_mask(self, pad_to: int):
        """Per-element 0/1 fp32 mask over ONE flat row, padded to
        ``pad_to`` — 1 exactly where ``adamw_update`` decays (leaves of
        original ndim >= 2); padding is 0 (never decayed)."""
        parts = [jnp.full((math.prod(s),), 1.0 if d else 0.0, jnp.float32)
                 for (s, _), d in zip(self.metas, self.decay)]
        m = jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)
        pad = pad_to - m.shape[0]
        if pad:
            m = jnp.concatenate([m, jnp.zeros((pad,), jnp.float32)])
        return m


def stack_layout(tree, *, stacked: bool = True) -> StackLayout:
    """Derive the :class:`StackLayout` of ``tree`` (abstract leaves OK)."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot build a StackLayout over an empty tree")
    if stacked:
        metas = tuple((tuple(l.shape[1:]), l.dtype) for l in leaves)
        length = leaves[0].shape[0]
        for l in leaves:
            if l.shape[0] != length:
                raise ValueError(
                    f"stacked leaves disagree on the stack length: "
                    f"{l.shape[0]} vs {length}")
    else:
        metas = tuple((tuple(l.shape), l.dtype) for l in leaves)
        length = 1
    decay = tuple(l.ndim >= 2 for l in leaves)
    elems = sum(math.prod(s) for s, _ in metas)
    return StackLayout(metas, decay, treedef, elems, length, stacked)


def resolve_prefetch_blocks(row_elems: int, n: int, N: int,
                            override: int = 0) -> int:
    """The B every lane_zero3 call site uses (shard layout, opt-state
    size, per-layer gather pipeline).  override > 0 wins; -1 (blocking
    negative control) gathers monolithically so B degenerates to 1;
    otherwise the cost model picks B from the DCN latency/bandwidth
    crossover on the per-chip stripe.  Capped so each block keeps at
    least one row per chip."""
    p = max(n * N, 1)
    if override > 0:
        b = override
    elif override < 0:
        b = 1
    else:
        b = optimal_prefetch_blocks(row_elems * 4 / p)
    return max(1, min(b, max(1, row_elems // p)))


def resolve_extras_prefetch_blocks(row_elems: int, n: int, N: int,
                                   override: int = 0) -> int:
    """Block count for the EXTRAS pseudo-layer (embed/head/norm tree).

    The extras row is not one more layer: with a real vocab its vocab·d
    embedding makes the row's gather payload dwarf a block row, so a
    positive ``--fsdp-prefetch`` override hand-tuned for the layer
    stack must NOT be inherited here — a B sized for a ~12·d² row
    starves the much larger extras gather of pipeline depth (and a B
    sized for extras over-splits the layers).  Only the blocking
    negative control (-1) passes through; any other override defers to
    the cost model on the extras row's OWN per-chip stripe.
    """
    return resolve_prefetch_blocks(row_elems, n, N,
                                   -1 if override < 0 else 0)


def shard_stack(tree, n: int, N: int, fsdp_prefetch: int = 0, *,
                stacked: bool = True):
    """Host-side: the (L, B, n·N, s) fp32 master layout of one stack.
    Place on the mesh with ``P(None, None, (*node_axes, lane_axis),
    None)`` and each chip's local block reshapes to the (L, B·s) shard
    the train step expects.  Returns (array, B).

    ``stacked=False`` is the extras pseudo-layer: its B resolves from
    its own row payload (:func:`resolve_extras_prefetch_blocks`), never
    from a positive override tuned for the layer stack."""
    layout = stack_layout(tree, stacked=stacked)
    resolve = resolve_prefetch_blocks if stacked \
        else resolve_extras_prefetch_blocks
    B = resolve(layout.row_elems, n, N, fsdp_prefetch)
    p = max(n * N, 1)
    flat = layout.flatten(tree, pad_to=B * p)
    s = flat.shape[1] // (B * p)
    return flat.reshape(layout.length, B, p, s), B


# ---------------------------------------------------------------------------
# per-family block specs (registered through the repro.comm registry seam)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What one model family declares to train through the sharded stack.

    stack_key        top-level params key of the scanned (L, ...) stack.
    replicated_keys  top-level keys that stay replicated on every chip
                     (Zamba2's ``shared_attn``: its weight-shared blocks
                     run at several layers per forward, so sharding them
                     would re-gather the same weights repeatedly; the
                     per-application adapters and projections stay
                     beside them); their gradients sync through the
                     bucketed ``lane`` path.
                     Every OTHER key (embed, final_norm, vis_proj,
                     encoder, ...) becomes the "extras" pseudo-layer:
                     1/p-sharded like one more stack row, gathered once
                     per step.
    make_body        ``make_body(cfg, params, *, positions, enc_out,
                     remat, x0) -> body(h, layer_params, layer_idx) ->
                     (h', aux)`` — the per-layer scan body
                     :func:`scan_stack` drives (``params`` carries the
                     replicated/extras trees the body may close over,
                     e.g. the hybrid shared blocks; ``x0`` is the token
                     embeddings, which the hybrid's shared blocks read).
    needs_extra_embeds
                     the family's forward requires an extra_embeds input
                     (vlm patches / audio frames) the training driver
                     does not synthesize — such families are excluded
                     from driver-level sweeps but still covered by the
                     layout/gather conformance grid.
    """
    family: str
    make_body: Callable
    stack_key: str = "blocks"
    replicated_keys: tuple = ()
    needs_extra_embeds: bool = False


def register_block_stack(family: str, **kw):
    """Sugar for ``register_impl("block_stack", family, auto_ok=False)``
    on a zero-arg-or-cfg spec factory ``fn(cfg) -> BlockSpec``."""
    return register_impl("block_stack", family, auto_ok=False, **kw)


def block_stack_spec(cfg) -> BlockSpec:
    """The registered :class:`BlockSpec` for ``cfg.family`` (imports the
    model assembly module so its registrations ran)."""
    import repro.models.transformer  # noqa: F401 - registers the specs
    if not has_impl("block_stack", cfg.family):
        raise ValueError(
            f"model family {cfg.family!r} has no registered block_stack "
            f"spec, so it cannot train through the lane_zero3 sharded "
            f"stack; registered families: {block_stack_families()}")
    return get_impl("block_stack", cfg.family).fn(cfg)


def block_stack_families() -> tuple:
    """Every lane-capable family, in registration order (the derived
    table the train-smoke sweep and the bench schema check enumerate)."""
    import repro.models.transformer  # noqa: F401 - registers the specs
    return strategies_for("block_stack")


# stable per-family smoke-arch preference: keeps the train-smoke sweep
# and the bench family_results "arch" column comparable across PRs even
# as new archs register (a family absent here falls back to the
# smallest-by-params smoke arch of that family)
_PREFERRED_SMOKE_ARCHS = {
    "dense": "llama3.2-3b",
    "moe": "granite-moe-3b-a800m",
    "ssm": "mamba2-780m",
    "hybrid": "zamba2-7b",
    "vlm": "llava-next-mistral-7b",
    "audio": "whisper-large-v3",
}


def family_smoke_archs(*, driver_trainable_only: bool = False) -> dict:
    """family -> smoke arch id.  The FAMILY list derives from the
    block-stack registry ("the registry IS the requirement": a family
    registration without a runnable model fails loudly); the arch per
    family follows ``_PREFERRED_SMOKE_ARCHS`` when valid — pinned so the
    bench trajectory's arch column stays comparable across PRs — and
    otherwise falls back to the family's smallest-by-params smoke arch.
    ``driver_trainable_only`` drops families whose BlockSpec declares
    ``needs_extra_embeds`` (the training driver cannot synthesize
    vlm patches / audio frames)."""
    from repro.configs import all_archs, resolve
    by_family: dict = {}
    for arch in all_archs():
        cfg = resolve(arch, smoke=True)
        cur = by_family.get(cfg.family)
        if cur is None or cfg.param_count() < cur[1]:
            by_family[cfg.family] = (arch, cfg.param_count())
    missing = [f for f in block_stack_families() if f not in by_family]
    if missing:
        raise ValueError(
            f"block_stack families with no registered arch: {missing}")
    registered = set(all_archs())
    out = {}
    for fam in block_stack_families():
        arch = _PREFERRED_SMOKE_ARCHS.get(fam)
        if arch not in registered:
            arch = by_family[fam][0]
        cfg = resolve(arch, smoke=True)
        if cfg.family != fam:
            raise ValueError(
                f"preferred smoke arch {arch!r} is family "
                f"{cfg.family!r}, not {fam!r}")
        spec = get_impl("block_stack", fam).fn(cfg)
        if driver_trainable_only and spec.needs_extra_embeds:
            continue
        out[fam] = arch
    return out


def split_params(spec: BlockSpec, params: dict):
    """Split a replicated params dict into (stack, extras, replicated)
    sub-dicts per the family spec.  ``extras`` is everything that is
    neither the stack nor explicitly replicated — the embeddings/
    final-norm tree the zero3 step shards as one more pseudo-layer."""
    if spec.stack_key not in params:
        raise ValueError(
            f"params have no {spec.stack_key!r} stack (keys: "
            f"{sorted(params)})")
    stack = params[spec.stack_key]
    repl = {k: params[k] for k in spec.replicated_keys if k in params}
    extras = {k: v for k, v in params.items()
              if k != spec.stack_key and k not in spec.replicated_keys}
    return stack, extras, repl
