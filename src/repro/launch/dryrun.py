import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any jax import (device count locks at
# first backend init).  This module is the ONLY place the flag is set —
# tests/benches see the real single CPU device.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, without allocating any real buffers
(ShapeDtypeStruct inputs only):

  * compiled.memory_analysis()  — proof the cell fits per-device HBM
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * a parse of the optimized HLO summing every collective's wire bytes,
    split ICI (intra-pod) vs DCN (cross-pod)  — the §Roofline collective
    term (cost_analysis does not include collectives)

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k [--multi]
  python -m repro.launch.dryrun --all          # every cell, subprocess each
  python -m repro.launch.dryrun --list
Results land in runs/dryrun/{single,multi}/<arch>__<shape>.json.
"""
import argparse
import dataclasses
import json
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs import resolve, all_archs, cells, SHAPES, RunConfig
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import init_model, init_cache
from repro.models.ssm import ssm_heads_axis
from repro.models.transformer import ServeState
from repro.optim import AdamWConfig, adamw_init
from repro.launch.mesh import make_production_mesh, batch_axes, mesh_sizes
from repro.launch import sharding as sh
from repro.launch.steps import build_train_step, build_prefill_step, \
    build_decode_step
from repro.launch import hlo_stats

RUNS = pathlib.Path(__file__).resolve().parents[3] / "runs" / "dryrun"


# ---------------------------------------------------------------------------
# per-cell planning
# ---------------------------------------------------------------------------

def _batch_axes_for(mesh, plan_name: str):
    """tp0 plan: the model axis joins the batch product (no TP)."""
    ba = batch_axes(mesh)
    if plan_name == "tp0":
        ba = (*ba, "model")
    return ba


def plan(cfg: ModelConfig, shape: ShapeConfig, mesh, *, micro_override=0,
         plan_name="default") -> RunConfig:
    nb = 1
    for a in _batch_axes_for(mesh, plan_name):
        nb *= mesh_sizes(mesh)[a]
    fsdp = cfg.param_count() > 10e9 or plan_name == "tp0"
    micro = 0
    if shape.kind == "train":
        b_loc = max(shape.global_batch // nb, 1)
        # per-µstep token budget: wide models halve it (activation bytes
        # scale with d_model; dbrx/qwen at 8192 tokens/µstep blow HBM)
        tok_budget = 8192 if cfg.d_model <= 4096 else 4096
        rows = max(1, tok_budget // shape.seq_len)
        micro = max(1, b_loc // rows)
    if micro_override:
        micro = micro_override
    # the sharding PLAN rides its own field; gradsync goes through a real
    # registry strategy ("auto" = cost-model dispatch) so the unknown-
    # strategy validation of RunConfig.__post_init__ stays armed
    return RunConfig(model=cfg, shape=shape, fsdp=fsdp,
                     remat="full" if shape.kind == "train" else "none",
                     microbatch=micro, gradsync="auto", plan=plan_name)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, ba=None):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, T = shape.global_batch, shape.seq_len
    if ba is None:
        ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh_sizes(mesh)[a]
    if B % nb:                       # tiny-batch cells: don't shard batch
        ba = ()
    tok_sh = NamedSharding(mesh, P(ba or None, None))
    emb_sh = NamedSharding(mesh, P(ba or None, None, None))
    i32 = jnp.int32
    d = cfg.d_model

    def tokens(n, t):
        return jax.ShapeDtypeStruct((n, t), i32, sharding=tok_sh)

    extra = None
    t_text = T
    if cfg.family == "vlm":
        t_text = T - cfg.vision_tokens
        extra = jax.ShapeDtypeStruct((B, cfg.vision_tokens, d),
                                     jnp.dtype(cfg.dtype), sharding=emb_sh)
    elif cfg.family == "audio":
        extra = jax.ShapeDtypeStruct((B, cfg.encoder_seq, d),
                                     jnp.dtype(cfg.dtype), sharding=emb_sh)
    if shape.kind == "train":
        return {"tokens": tokens(B, t_text), "labels": tokens(B, t_text),
                "extra": extra}
    if shape.kind == "prefill":
        return {"tokens": tokens(B, t_text), "extra": extra}
    return {"token": tokens(B, 1), "extra": extra}       # decode


def _cache_seq_spec(shape: ShapeConfig, mesh):
    """KV-cache seq dim: "model"; for tiny-batch long-context cells the
    batch axes join in (2-D sequence sharding) so B=1 doesn't strand the
    data axis."""
    ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh_sizes(mesh)[a]
    if shape.global_batch < nb:
        return tuple([*ba, "model"]), True
    return "model", False


# ---------------------------------------------------------------------------
# lowering per kind
# ---------------------------------------------------------------------------

def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               micro_override: int = 0, plan_name: str = "default",
               accum_bf16: bool = False):
    # mesh context: with_sharding_constraint inside the step functions uses
    # bare PartitionSpecs (spec item 3: ``with mesh: lowered = jax.jit(...)``)
    from repro.models.transformer import activation_batch_axes
    ba = _batch_axes_for(mesh, plan_name)
    nb = 1
    for a in ba:
        nb *= mesh_sizes(mesh)[a]
    pin = ba if shape.global_batch % nb == 0 else None
    # residual/FFN feature dims shard over "model" between layers for the
    # full-sequence kinds (train backward saves, prefill MoE buffers);
    # decode works on length-1 tensors where the extra gathers cost more
    # than the bytes
    d_axis = ("model" if plan_name == "default"
              and shape.kind in ("train", "prefill")
              and cfg.d_model % mesh_sizes(mesh).get("model", 1) == 0
              else None)
    kv = None
    if shape.kind in ("prefill", "decode"):
        seq_spec, seq2d = _cache_seq_spec(shape, mesh)
        kv = ((None if seq2d else ba), seq_spec)
    with mesh, activation_batch_axes(pin, d_axis, kv=kv):
        return _lower_cell(cfg, shape, mesh, micro_override, plan_name,
                           accum_bf16)


def _lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
                micro_override: int = 0, plan_name: str = "default",
                accum_bf16: bool = False):
    run = plan(cfg, shape, mesh, micro_override=micro_override,
               plan_name=plan_name)
    ins = input_specs(cfg, shape, mesh,
                      ba=_batch_axes_for(mesh, plan_name))
    pshapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    pspecs = sh.param_pspecs(pshapes, cfg, mesh, fsdp=run.fsdp,
                             tp=plan_name != "tp0")
    p_sds = sh.sds(pshapes, pspecs, mesh)
    B, T = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(adamw_init, pshapes)
        ospecs = sh.opt_pspecs(pspecs)
        o_sds = sh.sds(opt_shapes, ospecs, mesh)
        # --accum-bf16 forces bf16; otherwise defer to run.accum_dtype
        # (build_train_step resolves None from the RunConfig)
        step = build_train_step(cfg, run, AdamWConfig(),
                                _batch_axes_for(mesh, plan_name),
                                accum_dtype=jnp.bfloat16 if accum_bf16
                                else None)
        jitted = jax.jit(
            step,
            in_shardings=(sh.to_shardings(pspecs, mesh),
                          sh.to_shardings(ospecs, mesh),
                          ins["tokens"].sharding, ins["labels"].sharding,
                          None if ins["extra"] is None
                          else ins["extra"].sharding),
            out_shardings=(NamedSharding(mesh, P()),
                           sh.to_shardings(pspecs, mesh),
                           sh.to_shardings(ospecs, mesh)),
            donate_argnums=(0, 1))
        args = (p_sds, o_sds, ins["tokens"], ins["labels"], ins["extra"])
        lowered = jitted.lower(*args)
        return lowered, run

    seq_spec, seq2d = _cache_seq_spec(shape, mesh)
    ba = batch_axes(mesh)
    bspec = None if seq2d else ba   # B=1: don't shard batch

    def cache_shapes(max_seq):
        return jax.eval_shape(
            lambda: init_cache(cfg, B, max_seq, dtype=jnp.dtype(cfg.dtype)))

    def cache_specs(cshapes):
        def rule(path, leaf):
            ps = sh._path_str(path)
            nd = len(leaf.shape)
            spec = [None] * nd
            if ps in ("k", "v") or ps.endswith("/k") or ps.endswith("/v"):
                spec[nd - 4] = bspec
                spec[nd - 3] = seq_spec
            elif "conv" in ps:
                spec[1] = bspec
                if "conv_x" in ps:
                    spec[-1] = "model"
            elif ps.endswith("ssm"):
                spec[1] = bspec
                spec[ssm_heads_axis(cfg)] = "model"
            return P(*spec)
        specs = jax.tree_util.tree_map_with_path(rule, cshapes)
        return sh.sanitize_specs(cshapes, specs, mesh)

    if shape.kind == "prefill":
        t_text = ins["tokens"].shape[1]
        cshapes = cache_shapes(T)
        cspecs = cache_specs(cshapes)
        c_sds = sh.sds(cshapes, cspecs, mesh)
        step = build_prefill_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(sh.to_shardings(pspecs, mesh),
                          ins["tokens"].sharding,
                          sh.to_shardings(cspecs, mesh),
                          None if ins["extra"] is None
                          else ins["extra"].sharding),
            donate_argnums=(2,))
        lowered = jitted.lower(p_sds, ins["tokens"], c_sds, ins["extra"])
        return lowered, run

    # decode: cache holds seq_len context, one new token
    cshapes = cache_shapes(T)
    cspecs = cache_specs(cshapes)
    c_sds = sh.sds(cshapes, cspecs, mesh)
    lsharding = NamedSharding(mesh, P(bspec))
    l_sds = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=lsharding)
    enc_sds = None
    if cfg.family == "audio":
        ekv = jax.eval_shape(
            lambda: {"k": jnp.zeros((cfg.num_layers, B, cfg.encoder_seq,
                                     cfg.num_kv_heads, cfg.hd()),
                                    jnp.dtype(cfg.dtype)),
                     "v": jnp.zeros((cfg.num_layers, B, cfg.encoder_seq,
                                     cfg.num_kv_heads, cfg.hd()),
                                    jnp.dtype(cfg.dtype))})
        espec = jax.tree.map(lambda _: P(None, bspec, None, None, None),
                             jax.tree.map(lambda x: 0, ekv))
        espec = {"k": P(None, bspec, None, None, None),
                 "v": P(None, bspec, None, None, None)}
        enc_sds = sh.sds(ekv, espec, mesh)
    state_sds = ServeState(cache=c_sds, length=l_sds, enc_kv=enc_sds)
    state_shardings = ServeState(
        cache=sh.to_shardings(cspecs, mesh), length=lsharding,
        enc_kv=None if enc_sds is None else
        jax.tree.map(lambda s: s.sharding, enc_sds))
    step = build_decode_step(cfg)
    jitted = jax.jit(step,
                     in_shardings=(sh.to_shardings(pspecs, mesh),
                                   ins["token"].sharding, state_shardings),
                     donate_argnums=(2,))
    lowered = jitted.lower(p_sds, ins["token"], state_sds)
    return lowered, run


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        total += n * _DTYPE_BYTES[dt]
    return total


def _line_group(line: str, pod_size: int):
    return hlo_stats.group_info(line, pod_size)


_CONV_RE = re.compile(r"=\s*f32\[([\d,]+)\][^\s]*\s+"
                      r"(?:convert|fusion)\(")


def _f32_mirror_bytes(hlo: str, floor: int = 256 * 2**20) -> int:
    """XLA:CPU computes dots through fp32 and hoists whole-stack operand
    conversions out of loops, keeping fp32 MIRRORS of large bf16 buffers
    (KV-cache stacks, MoE buffers) that do not exist on TPU, where the MXU
    consumes bf16 directly.  Sum the distinct ≥256 MB fp32 convert outputs
    so the roofline/memory report can state a TPU-adjusted figure."""
    seen: dict[tuple, int] = {}
    for line in hlo.splitlines():
        if "f32[" not in line or ("convert" not in line
                                  and "wrapped_convert" not in line):
            continue
        m = _CONV_RE.search(line)
        if not m:
            continue
        dims = tuple(int(x) for x in m.group(1).split(",") if x)
        b = 4
        for d in dims:
            b *= d
        if b >= floor:
            seen[dims] = b
    return int(sum(seen.values()))


def collect_collectives(hlo: str, *, pod_size: int = 256) -> dict:
    """Sum wire bytes per collective kind from optimized HLO (per device).

    Wire model (ring algorithms, per participating device):
      all-reduce 2(g-1)/g·b   all-gather (g-1)·b_in ≈ (g-1)/g·b_out
      reduce-scatter (g-1)/g·b_in   all-to-all (g-1)/g·b   permute b
    where b is the op's result byte size on this device.
    """
    per_kind: dict[str, dict] = {}
    dcn_bytes = 0.0
    ici_bytes = 0.0
    for line in hlo.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(type_str)
        gsz, dcn = _line_group(line, pod_size)
        g = gsz or 2
        if kind == "all-reduce":
            wire = 2 * (g - 1) / g * b
        elif kind == "all-gather":
            wire = (g - 1) / g * b
        elif kind == "reduce-scatter":
            wire = (g - 1) / g * b          # b here = input tuple size
        elif kind == "all-to-all":
            wire = (g - 1) / g * b
        else:                                # collective-permute
            wire = float(b)
        rec = per_kind.setdefault(kind, {"count": 0, "bytes": 0.0,
                                         "wire_bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += b
        rec["wire_bytes"] += wire
        if dcn:
            dcn_bytes += wire
        else:
            ici_bytes += wire
    return {"per_kind": per_kind,
            "total_bytes": sum(r["bytes"] for r in per_kind.values()),
            "total_wire_bytes": sum(r["wire_bytes"]
                                    for r in per_kind.values()),
            "dcn_wire_bytes": dcn_bytes, "ici_wire_bytes": ici_bytes}


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path | None = None, *,
             micro_override: int = 0, plan_name: str = "default",
             tag: str = "", accum_bf16: bool = False) -> dict:
    cfg = resolve(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, run = lower_cell(cfg, shape, mesh,
                              micro_override=micro_override,
                              plan_name=plan_name, accum_bf16=accum_bf16)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
                 if hasattr(mem, k)}
    except Exception as e:  # noqa: BLE001
        mem_d = {"error": str(e)[:200]}

    try:
        cost = dict(compiled.cost_analysis())
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float))}
    except Exception as e:  # noqa: BLE001
        cost = {"error": str(e)[:200]}

    hlo = compiled.as_text()
    colls = collect_collectives(hlo)
    f32_mirror = _f32_mirror_bytes(hlo)
    try:
        stats = hlo_stats.analyze(hlo)
        stats.pop("coll", None)
    except Exception as e:  # noqa: BLE001
        stats = {"error": str(e)[:300]}

    nchips = 512 if multi_pod else 256
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": nchips,
        "params": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
        "fsdp": run.fsdp, "microbatch": run.microbatch, "remat": run.remat,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_d,
        "f32_mirror_bytes": f32_mirror,
        "cost_analysis": cost,
        "collectives": colls,          # bodies-once view (cross-check)
        "hlo_stats": stats,            # trip-count-corrected totals
        "hlo_bytes": len(hlo),
    }
    result["plan"] = run.plan
    if out_dir is not None:
        import gzip
        out_dir.mkdir(parents=True, exist_ok=True)
        stem = f"{arch}__{shape_name}" + (f"__{tag}" if tag else "")
        path = out_dir / f"{stem}.json"
        path.write_text(json.dumps(result, indent=1))
        # keep the optimized HLO so roofline/perf iterations can re-analyze
        # without recompiling
        with gzip.open(out_dir / f"{stem}.hlo.gz", "wt") as f:
            f.write(hlo)
        result["json"] = str(path)
    return result


def list_cells():
    rows = []
    for a in all_archs():
        cfg = resolve(a)
        for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            if s == "long_500k" and not cfg.subquadratic:
                rows.append((a, s, "SKIP (full attention; DESIGN.md §4)"))
            else:
                rows.append((a, s, "run"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--accum-bf16", action="store_true")
    ap.add_argument("--plan", default="default", choices=["default", "tp0"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    if args.list:
        for a, s, st in list_cells():
            print(f"{a:28s} {s:12s} {st}")
        return 0

    if args.all:
        fails = []
        meshes = [False, True] if args.both_meshes else [args.multi]
        for multi in meshes:
            sub = RUNS / ("multi" if multi else "single")
            for a, s, st in list_cells():
                if st != "run":
                    continue
                if args.skip_existing and (sub / f"{a}__{s}.json").exists():
                    print(f"skip existing {a} {s}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", a, "--shape", s] + \
                      (["--multi"] if multi else [])
                print(f"=== {a} {s} {'multi' if multi else 'single'} ===",
                      flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=7200)
                tail = (r.stdout + r.stderr).strip().splitlines()[-8:]
                print("\n".join(tail), flush=True)
                if r.returncode != 0:
                    fails.append((a, s, multi))
        print(f"\nFAILED CELLS: {fails if fails else 'none'}")
        return len(fails)

    out = RUNS / ("multi" if args.multi else "single")
    res = run_cell(args.arch, args.shape, args.multi, out,
                   micro_override=args.microbatch, plan_name=args.plan,
                   tag=args.tag, accum_bf16=args.accum_bf16)
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("hlo_bytes",)}, indent=1))
    print(f"memory_analysis: {res['memory_analysis']}")
    print(f"cost_analysis flops={res['cost_analysis'].get('flops')}")
    print(f"collectives total wire bytes="
          f"{res['collectives']['total_wire_bytes']:.3e}")
    print("DRYRUN OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
