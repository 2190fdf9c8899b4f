"""Sharding rules: parameter PartitionSpecs and input specs.

Conventions (DESIGN.md §3):
  batch axes   ("pod","data")  — token batch, serve batch
  "model"      — tensor parallelism: attention-head / d_ff / vocab columns,
                 SSM heads, expert d_ff; KV-cache *sequence* dim for decode
  fsdp         — when enabled, the non-TP weight dim additionally shards
                 over "data" (ZeRO-3-style; the per-layer all-gathers are
                 inserted by GSPMD inside the layer scan)

KV projections replicate over "model" when num_kv_heads doesn't divide the
TP degree (GQA kv < tp) — the standard Megatron-GQA fallback.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs.base import ModelConfig
from .mesh import batch_axes, mesh_sizes


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


def _base_spec(ps: str, cfg: ModelConfig, tp: int, fsdp):
    """Final-dims partition spec for one parameter, by path suffix."""
    kv_shardable = cfg.num_kv_heads and cfg.num_kv_heads % tp == 0
    if ps.endswith("embed/tok"):
        return ("model", None)
    if ps.endswith("embed/head"):
        return (fsdp, "model")
    if ps.endswith("attn/wq") or ps.endswith("xattn/wq"):
        return (fsdp, "model")
    if ps.endswith("/wk") or ps.endswith("/wv"):
        return (fsdp, "model") if kv_shardable else (fsdp, None)
    if ps.endswith("/bq"):
        return ("model",)
    if ps.endswith("/bk") or ps.endswith("/bv"):
        return ("model",) if kv_shardable else (None,)
    if ps.endswith("/wo"):
        return ("model", fsdp)
    if ps.endswith("mlp/w_up") or ps.endswith("mlp/w_gate"):
        return (fsdp, "model")
    if ps.endswith("mlp/w_down"):
        return ("model", fsdp)
    if ps.endswith("moe/router"):
        return (fsdp, None)
    if ps.endswith("moe/w_up") or ps.endswith("moe/w_gate"):
        return (None, fsdp, "model")
    if ps.endswith("moe/w_down"):
        return (None, "model", fsdp)
    if ps.endswith("mamba/w_z") or ps.endswith("mamba/w_x"):
        return (fsdp, "model")
    if ps.endswith("mamba/w_dt"):
        return (fsdp, "model")
    if ps.endswith("mamba/w_B") or ps.endswith("mamba/w_C"):
        return (fsdp, None)
    if ps.endswith("conv_x_w"):
        return (None, "model")
    if ps.endswith("conv_x_b"):
        return ("model",)
    if "conv_B" in ps or "conv_C" in ps:
        return None                      # replicated, any rank
    if ps.endswith("A_log") or ps.endswith("/D") or ps.endswith("dt_bias"):
        return ("model",)
    if ps.endswith("mamba/norm/scale"):
        return ("model",)
    if ps.endswith("mamba/out_proj"):
        return ("model", fsdp)
    if ps.endswith("vis_proj") or ps.endswith("encoder/pos"):
        return None
    # norms, biases, anything else: replicated
    return None


def param_pspecs(params_shapes: Any, cfg: ModelConfig, mesh, *,
                 fsdp: bool, tp: bool = True) -> Any:
    """PartitionSpec tree matching the (eval_shape'd) params tree.

    tp=False is the TP-free plan (small models on big meshes): the "model"
    axis joins the batch/FSDP product instead of column-sharding weights —
    same 16×16 mesh, zero tensor-parallel collectives.
    """
    tp_deg = mesh_sizes(mesh).get("model", 1) if tp else 1
    if tp:
        fs = "data" if (fsdp and "data" in mesh.axis_names) else None
    else:
        fs = (("data", "model") if fsdp else None)

    def sub(e):
        if e == "model":
            return "model" if tp else None
        return e

    def rule(path, leaf):
        ps = _path_str(path)
        base = _base_spec(ps, cfg, tp_deg, fs)
        nd = len(leaf.shape)
        if base is None:
            return P()
        base = [sub(e) for e in base]
        lead = nd - len(base)
        if lead < 0:
            raise RuntimeError(
                f"spec {ps} has more sharded dims than leaf shape "
                f"{leaf.shape} (base {base})")
        return P(*([None] * lead + list(base)))

    specs = jax.tree_util.tree_map_with_path(rule, params_shapes)
    return sanitize_specs(params_shapes, specs, mesh)


def opt_pspecs(param_specs: Any) -> Any:
    """AdamW moments mirror params; count is replicated."""
    return {"m": param_specs, "v": param_specs,
            "count": P()}


def sanitize_specs(shapes_tree: Any, spec_tree: Any, mesh) -> Any:
    """Drop spec entries whose mesh-axis product doesn't evenly divide the
    dimension (in/out shardings must divide; e.g. whisper's vocab 51866 on a
    16-way axis).  The dropped dim becomes replicated."""
    sizes = mesh_sizes(mesh)

    def fix(leaf, spec):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = []
        for dim, e in zip(leaf.shape, entries):
            if e is None:
                out.append(None)
                continue
            axes = (e,) if isinstance(e, str) else tuple(e)
            n = 1
            for a in axes:
                n *= sizes[a]
            out.append(e if dim % n == 0 else None)
        return P(*out)

    return jax.tree.map(fix, shapes_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def to_shardings(spec_tree: Any, mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh) -> P:
    return P(batch_axes(mesh))


def token_spec(mesh) -> P:
    return P(batch_axes(mesh), None)


def embed_spec(mesh) -> P:
    return P(batch_axes(mesh), None, None)


def sds(tree, spec_tree, mesh):
    """ShapeDtypeStructs with shardings attached, for .lower()."""
    shardings = to_shardings(spec_tree, mesh)
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)
