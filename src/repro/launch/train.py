"""Training driver: end-to-end loop with checkpoint/restart + fault hooks.

The step resolves through the ``repro.comm`` "train_step" registry
(``build_train_step_lane``): ``--gradsync`` accepts every registered
strategy (derived from the registry, incl. ``auto``, the ZeRO flavors
and the quorum-degraded ``lane_quorum``), ``--gradsync-buckets`` /
``--fsdp-prefetch`` are the §5 tuning knobs, and the master
parameter/optimizer layout (replicated vs ZeRO-1 flat moments vs the
ZeRO-3 (L, B, p, s) layer masters) follows ``LaneComm.param_layout`` via
``launch.steps.init_lane_train_state`` — checkpoints canonicalize
through the matching layout so a ``lane_zero3`` checkpoint written at p
chips restores bit-identically at p′ chips.

Examples
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt runs/ckpt_demo \
      --gradsync lane_zero3 --pods 2
  (production: same entry point under one process per host with
   jax.distributed.initialize(); the mesh comes from launch/mesh.py)

Fault tolerance — the recovery ladder (HEALTHY → DEGRADED → RESTART):
  * ``--fault-plan`` injects a deterministic runtime.faults.FaultPlan
    (pod_slow / pod_lost / ckpt_io / corrupt_leaf) so every rung runs
    under tier-1 with no real hardware; ``seed:<n>`` draws a seeded
    random plan.
  * a runtime.watchdog.Watchdog folds per-pod progress heartbeats into
    the 0/1 contributing mask; under ``--gradsync lane_quorum`` the
    step takes that mask and DEGRADED steps proceed with the
    quorum-rescaled gradient (masked pods contribute zero; their
    (seed, step)-keyed microbatch rows are logged and replayable).
  * runtime.health.HealthMonitor bounds the staleness
    (``--quorum-staleness`` K): a pod masked for more than K
    consecutive steps — or ANY masked pod under a strategy with no
    quorum path — escalates to RESTART: emergency checkpoint, then
    ``plan_elastic_mesh`` re-plans around the lost pod's devices and
    the attempt loop resumes on the survivors (``--max-restarts``
    bounds it).  The in-process restart is bit-identical to killing
    the job and re-launching with ``--lose-chips``.
  * resume: picks up from the newest checkpoint that VERIFIES (per-leaf
    crc32; a corrupt latest falls back to the previous committed step);
    the data pipeline is (seed, step)-keyed so the token stream
    continues exactly
  * SIGTERM → emergency checkpoint before exit (preemption handling);
    the emergency save records the last COMPLETED step, never a step
    that raised or was interrupted mid-flight
  * elastic restart: ``--lose-chips`` re-plans the mesh around lost
    devices (runtime.elastic) and the layout-aware restore re-shards the
    canonical checkpoint onto the survivors
  * async checkpoint writer off the critical path with bounded
    retry-with-backoff for transient I/O errors; worker errors surface
    on the emergency path instead of dying with the daemon thread
"""
from __future__ import annotations

import argparse
import math
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import resolve, RunConfig
from repro.configs.base import ShapeConfig
from repro.models import init_model
from repro.optim import AdamWConfig
from repro.checkpoint import AsyncCheckpointer, latest_step
from repro.data import make_loader
from repro.launch.mesh import batch_axes
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (LaneTrainState, build_train_step_lane,
                                init_lane_train_state,
                                restore_lane_train_state)
from repro.runtime.elastic import plan_elastic_mesh
from repro.runtime.faults import FaultPlan, corrupt_leaf_file
from repro.runtime.health import DEGRADED, RESTART, HealthMonitor
from repro.runtime.watchdog import Watchdog


def make_mesh_auto(batch: int = 1 << 30, pods: int = 1, tp: int = 1):
    """Widest (data, model) factorization of the local devices that still
    divides ``batch``; ``pods > 1`` adds the cross-DCN "pod" axis (the
    lane level) as the outermost batch axis.  ``tp > 1`` pins the
    "model" axis to exactly that size (tensor parallelism): the mesh
    becomes the full 3D ``pods × data × model`` grid with data taking
    everything the pod and model axes leave."""
    n = len(jax.devices())
    pods = max(pods, 1)
    tp = max(tp, 1)
    if n % pods:
        raise ValueError(f"{n} devices not divisible into {pods} pods")
    if pods > 1 and batch % pods:
        # fail here with the real reason, not deep inside shard_map's
        # divisibility machinery
        raise ValueError(
            f"global batch {batch} not divisible by the {pods}-pod lane "
            f"axis; pick a batch divisible by --pods")
    per = n // pods
    if tp > 1:
        if per % tp:
            raise ValueError(
                f"{per} devices per pod not divisible by "
                f"--model-parallel {tp}")
        d = per // tp
        if batch % max(pods * d, 1):
            raise ValueError(
                f"global batch {batch} not divisible by the {pods}×{d} "
                f"batch grid that --model-parallel {tp} leaves on "
                f"{n} devices; pick a divisible batch (or change "
                f"--pods/--model-parallel)")
        if pods > 1:
            return jax.make_mesh((pods, d, tp), ("pod", "data", "model"))
        return jax.make_mesh((d, tp), ("data", "model"))
    d = 1
    while d * 2 <= per and per % (d * 2) == 0 \
            and batch % (pods * d * 2) == 0:
        d *= 2
    m = per // d
    if pods > 1:
        return jax.make_mesh((pods, d, m), ("pod", "data", "model"))
    if n == 1:
        return jax.make_mesh((1, 1), ("data", "model"))
    return jax.make_mesh((d, m), ("data", "model"))


def _tree_alive(tree) -> bool:
    """False when any leaf buffer was deleted (donated into a step call
    that raised) — an emergency save would die on device_get."""
    return all(not (hasattr(l, "is_deleted") and l.is_deleted())
               for l in jax.tree.leaves(tree))


def _resolve_pods(pods: int, gradsync: str) -> int:
    """0 = auto: lane_zero3 needs distinct lane/node batch axes, so give
    it a pod axis whenever the device count allows; everything else
    defaults to the single-pod mesh."""
    if pods:
        return pods
    n = len(jax.devices())
    if gradsync == "lane_zero3" and n >= 4 and n % 2 == 0:
        return 2
    return 1


def _outer_axis(mesh0) -> int:
    """Index of the outermost batch axis (the lane/pod level) — the axis
    plan_elastic_mesh shrinks and the watchdog's quorum is over."""
    names = mesh0.axis_names
    for a in ("pod", "data"):
        if a in names:
            return names.index(a)
    raise ValueError(f"no batch axis in {names}")


def _restart_flat_indices(mesh0, lost, pod_ranks) -> list:
    """Map CURRENT-mesh lane ranks the health ladder condemned back to
    ORIGINAL-mesh flat device indices.

    The current mesh is the original minus the outer-axis slices that
    contain ``lost``; the surviving outer coordinates, in order, ARE the
    current lane ranks.  Returning original-mesh indices keeps one
    canonical bookkeeping: replanning from (mesh0, lost ∪ these) is
    byte-for-byte the ``--lose-chips`` path, so an in-process restart is
    bit-identical to a fresh launch that lost the same pods.
    """
    shape0 = mesh0.devices.shape
    outer = _outer_axis(mesh0)
    dropped = {np.unravel_index(i, shape0)[outer] for i in lost}
    survivors = [c for c in range(shape0[outer]) if c not in dropped]
    out = []
    for q in pod_ranks:
        coord = survivors[q]
        out.extend(i for i in range(math.prod(shape0))
                   if np.unravel_index(i, shape0)[outer] == coord)
    return sorted(out)


def _post_commit_faults(ckpt, plan: FaultPlan, ckpt_dir: str,
                        step: int) -> None:
    """Apply any corrupt_leaf fault scheduled for ``step`` — AFTER the
    async commit lands (wait), so the crc machinery (not the atomic
    rename) is what must catch it."""
    leaf = plan.corrupt_at(step)
    if leaf is not None:
        ckpt.wait()
        p = corrupt_leaf_file(ckpt_dir, step, leaf)
        print(f"fault: corrupted {p} after commit "
              f"(restore must fall back via crc32)", flush=True)


def main(argv=None):
    from repro.comm import strategies_for
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    # strategy surface: choices DERIVE from the train_step registry, so a
    # new registration is immediately drivable (and testable) from here
    ap.add_argument("--gradsync", default="native",
                    choices=list(strategies_for("train_step")),
                    help="gradient-sync / parameter-layout strategy "
                         "(registry-derived; 'auto' = cost model)")
    ap.add_argument("--gradsync-buckets", type=int, default=0,
                    help="bucket count K; 0 = cost-model auto")
    ap.add_argument("--fsdp-prefetch", type=int, default=0,
                    help="lane_zero3 gather blocks B; 0 = auto, "
                         "-1 = blocking negative control")
    ap.add_argument("--fsdp-regather", action="store_true",
                    help="lane_zero3 backward re-gather: re-run each "
                         "layer's weight gather in the backward under "
                         "remat (backward residuals stay 1/p)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="gradient-accumulation microbatches per step "
                         "(0 = off); the LOCAL batch must divide by it")
    ap.add_argument("--accum-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="microbatch gradient accumulator precision")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel degree: pins the mesh 'model' "
                         "axis to this size; MLP activation collectives "
                         "route through model-axis (collective, "
                         "strategy) cells (1 = off)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="MoE expert parallelism: token routing as the "
                         "decomposed moe_route alltoall over the batch "
                         "axes; under lane_zero3 the expert weights "
                         "live in a never-gathered E/p local master")
    ap.add_argument("--ep-blocks", type=int, default=1,
                    help="capacity-dim pipeline depth of the routing "
                         "alltoall (block j+1's dispatch overlaps block "
                         "j's expert FFN; 1 = sequential)")
    ap.add_argument("--pods", type=int, default=0,
                    help="pod (lane) axis size; 0 = auto (lane_zero3 "
                         "gets 2 when devices allow, else 1)")
    ap.add_argument("--lose-chips", default="",
                    help="comma-separated flat device indices to treat "
                         "as lost: re-plan the mesh around them "
                         "(elastic restart on survivors)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault injection: "
                         "'kind@step[-until][:k=v,...];...' (kinds "
                         "pod_slow/pod_lost/ckpt_io/corrupt_leaf, see "
                         "runtime.faults) or 'seed:<n>' for a seeded "
                         "random plan")
    ap.add_argument("--tune", action="store_true",
                    help="probe the live topology's collective timings "
                         "before training (repro.tuning): measured "
                         "costs then outrank the closed-form model in "
                         "auto dispatch; results merge into the cache")
    ap.add_argument("--tuning-cache", default="",
                    help="timing-cache path (default: tuning_cache.json "
                         "inside --ckpt when one is set); restored "
                         "entries feed dispatch without re-probing")
    ap.add_argument("--quorum-staleness", type=int, default=2,
                    help="K: consecutive steps a pod may be masked out "
                         "of the quorum before DEGRADED escalates to "
                         "RESTART")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="in-process elastic restarts before giving up")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = resolve(args.arch, smoke=args.smoke)
    mesh0 = make_mesh_auto(args.batch,
                           _resolve_pods(args.pods, args.gradsync),
                           tp=args.model_parallel)
    if args.fault_plan.startswith("seed:"):
        num_pods0 = mesh0.devices.shape[_outer_axis(mesh0)]
        plan = FaultPlan.generate(int(args.fault_plan[len("seed:"):]),
                                  args.steps, num_pods0)
        print(f"fault plan (seeded): {plan.faults}")
    else:
        plan = FaultPlan.parse(args.fault_plan)
    lost = set()
    if args.lose_chips:
        lost = {int(x) for x in args.lose_chips.split(",") if x != ""}

    # the recovery-ladder attempt loop: each RESTART returns the lost
    # pods' ORIGINAL-mesh device indices and the next attempt replans —
    # exactly the --lose-chips path, so the in-process restart is
    # bit-identical to a fresh launch on the survivors
    for attempt in range(args.max_restarts + 1):
        rc, more = _run_attempt(args, cfg, plan, mesh0, sorted(lost))
        if more is None:
            return rc
        lost |= set(more)
        print(f"restart {attempt + 1}/{args.max_restarts}: re-planning "
              f"around lost devices {sorted(lost)}", flush=True)
    print(f"giving up after {args.max_restarts} restarts",
          file=sys.stderr, flush=True)
    return 1


def _tuning_cache_path(args) -> str:
    """Where the timing cache lives: ``--tuning-cache`` if given, else
    beside the checkpoints, else nowhere ("")."""
    import os
    from repro.tuning import DEFAULT_CACHE_NAME
    return args.tuning_cache or (
        os.path.join(args.ckpt, DEFAULT_CACHE_NAME) if args.ckpt else "")


def _setup_tuner(args, mesh, ba):
    """Restore/probe the timing cache and return a Tuner (or None).

    The cache rides in the checkpoint directory by default
    (``--tuning-cache`` overrides), so a resumed run re-ranks with the
    same measured costs it committed to — measure once, then commit.
    A missing or corrupt cache degrades to the closed-form model; with
    ``--tune`` the probe fills (only) unmeasured cells — the ladder
    sweep PLUS the persisted cache-miss worklist (payload sizes a
    previous run's dispatch asked for but the cache could not answer) —
    and the merged table is saved back atomically, consuming the
    worklist.
    """
    from repro.core.lane import LaneTopology
    from repro.tuning import (DEFAULT_LADDER, SMOKE_LADDER, TimingTable,
                              Tuner, load_timing_table_or_none,
                              probe_cells, save_timing_table)
    from repro.tuning.probe import probe_worklist
    from repro.tuning.store import load_misses
    cache_path = _tuning_cache_path(args)
    if not cache_path and not args.tune:
        return None
    table = (load_timing_table_or_none(cache_path)
             if cache_path else None) or TimingTable()
    if args.tune:
        topo = LaneTopology(node_axes=ba[1:], lane_axis=ba[0])
        ladder = SMOKE_LADDER if args.smoke else DEFAULT_LADDER
        probe_cells(mesh, topo, ladder=ladder, table=table)
        worklist = load_misses(cache_path) if cache_path else []
        if worklist:
            probed = probe_worklist(mesh, topo, worklist, table=table)
            print(f"tuning worklist: {probed}/{len(worklist)} recorded "
                  f"misses probed", flush=True)
        if cache_path:
            save_timing_table(cache_path, table)
            print(f"tuning cache committed: {cache_path} "
                  f"({len(table)} cells)", flush=True)
    return Tuner(table) if len(table) else None


def _adopt_fitted_hw(tuner) -> None:
    """Install the timing-cache-fitted HW constants BEFORE step building.

    When a run has a measured timing table (a restored cache or a fresh
    ``--tune`` probe), the closed-form cost model should price with
    constants fitted to THAT topology (tuning.fit.fit_hw), not the
    shipped defaults — and the install must happen before
    build_train_step_lane / init_lane_train_state so the K/B layout
    resolutions the run (and its checkpoint geometry) commit to are
    priced against the same constants end to end.  Unfittable tables
    (too few cells) degrade to the defaults, loudly."""
    if tuner is None:
        return
    from repro.core.costmodel import set_hw
    from repro.tuning.fit import fit_hw
    try:
        fit = fit_hw(tuner.table)
    except ValueError as e:
        print(f"fitted-HW adoption skipped ({e}); cost model keeps the "
              f"shipped constants", flush=True)
        return
    set_hw(fit.hw)
    print(f"cost-model HW adopted from measured timing cache: "
          f"{fit.num_cells} cells, residual rms "
          f"{fit.residual_rms_us:.1f}us / max {fit.residual_max_us:.1f}us",
          flush=True)


def _commit_tuner_misses(args, tuner) -> None:
    """Persist the misses dispatch accumulated this run so the next
    ``--tune`` launch probes exactly those cells (the "commit" half of
    measure-once-then-commit for payloads the ladder never covered).
    Best-effort: a failed write must not fail a finished run."""
    from repro.tuning import save_timing_table
    cache_path = _tuning_cache_path(args)
    if not (cache_path and tuner is not None and tuner.misses):
        return
    try:
        save_timing_table(cache_path, tuner.table, misses=tuner.misses)
        uniq = len(dict.fromkeys(tuple(m) for m in tuner.misses))
        print(f"tuning misses committed: {uniq} cells queued for the "
              f"next --tune pass ({cache_path})", flush=True)
    except OSError as e:
        print(f"WARNING: tuning miss commit failed: {e}",
              file=sys.stderr, flush=True)


def _lane_state(cfg, run, mesh, comm, seed: int):
    """``(st, init)``: the run's master layout and a thunk that makes it.

    ``st`` holds shapes, not arrays: its specs and checkpoint layout come
    from an abstract trace of ``init_lane_train_state``.  ``init()`` runs
    the same function under one jit whose outputs carry the mesh
    shardings, so each array is created already sharded: no chip first
    holds the whole params, ZeRO masters or moments, and no initial copy
    stays alive beside the state the step is given.
    """
    key = jax.random.PRNGKey(seed)
    static = {}
    n, N = comm.topo.sizes(mesh)
    # a ZeRO-3 stack is generated layer-sharded over the batch-axes chips
    # (each chip draws its own L/p layers); GSPMD then re-lays it into the
    # 1/p stripes with an all-to-all.  Left alone, every chip would draw
    # the whole stack and slice its stripe out of it.
    by_layer = (comm.param_layout(run.gradsync) == "zero3"
                and cfg.num_layers % (n * N) == 0)
    # the init program runs on an Auto-typed view of the mesh: with
    # explicit axes the sharding types force the stack whole again
    auto = Mesh(mesh.devices, mesh.axis_names)
    layers = NamedSharding(auto, P(batch_axes(mesh)))

    def arrays(k):
        params = init_model(k, cfg)
        if by_layer:
            params["blocks"] = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, layers),
                params["blocks"])
        st = init_lane_train_state(cfg, run, mesh, params, comm=comm)
        static["layout"] = (st.pspecs, st.ospecs, st.ckpt_layout)
        return st.params, st.opt_state

    params_t, opt_t = jax.eval_shape(arrays, key)
    st = LaneTrainState(params_t, opt_t, *static["layout"])
    init = jax.jit(arrays, out_shardings=st.to_shardings(mesh))
    return st, lambda: init(key)


def _run_attempt(args, cfg, plan: FaultPlan, mesh0, lost):
    """One attempt of the run on the mesh that survives ``lost``.

    Returns (rc, None) when the run completed (or legitimately stopped),
    or (None, new_lost_flat_indices) when the health ladder hit RESTART
    — the caller replans and tries again.
    """
    mesh = mesh0
    if lost:
        em = plan_elastic_mesh(mesh0.axis_names, mesh0.devices.shape, lost)
        mesh = em.make()
        print(f"elastic mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}"
              f" (lost {em.lost})")
    ba = batch_axes(mesh)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    run = RunConfig(model=cfg, shape=shape, remat=args.remat,
                    gradsync=args.gradsync,
                    gradsync_buckets=args.gradsync_buckets,
                    fsdp_prefetch=args.fsdp_prefetch,
                    fsdp_regather=args.fsdp_regather,
                    microbatch=args.microbatch,
                    accum_dtype=args.accum_dtype,
                    model_parallel=args.model_parallel,
                    expert_parallel=args.expert_parallel,
                    ep_blocks=args.ep_blocks)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)

    # measured-cost tuning (repro.tuning): restore the cache living
    # beside the checkpoints, optionally probe this topology (--tune;
    # measure-once — already-measured cells are skipped), and hand the
    # tuner to the step builder so auto dispatch ranks by measured cost.
    # The fitted-HW install happens HERE, before any step/layout
    # building: installing later would desync the K/B layout resolutions
    # the checkpoint geometry commits to from the constants pricing them.
    tuner = _setup_tuner(args, mesh, ba)
    _adopt_fitted_hw(tuner)

    # step first (it validates strategy × topology, e.g. lane_zero3 on a
    # single-batch-axis mesh), then the layout-matched master state
    step, comm = build_train_step_lane(cfg, run, opt_cfg, mesh, None,
                                       tuner=tuner)
    st, init_state = _lane_state(cfg, run, mesh, comm, args.seed)
    pshard, oshard = st.to_shardings(mesh)

    start_step = 0
    ckpt = AsyncCheckpointer(args.ckpt, layout=st.ckpt_layout) \
        if args.ckpt else None
    if args.ckpt and latest_step(args.ckpt) is not None:
        # the st trees are only the shape/layout targets here — don't
        # make a full init state just to overwrite it.
        # restore_lane_train_state handles BOTH same-kind restores and
        # cross-layout ones (a lane_zero3 checkpoint resuming under
        # lane_zero1 or a replicated strategy, and back) through the
        # canonical flat order — and falls back to the newest committed
        # step whose crc32s verify when the latest one rotted on disk
        (params, opt_state), start_step = restore_lane_train_state(
            args.ckpt, cfg, run, mesh, st,
            shardings=(pshard, oshard))
        print(f"resumed from step {start_step} "
              f"(layout {st.ckpt_layout.kind})")
    else:
        params, opt_state = init_state()

    # fault/quorum machinery: the watchdog folds heartbeats (driven by
    # the fault plan; on a real fleet, by per-host progress counters)
    # into the 0/1 contributing mask, and the health monitor runs the
    # HEALTHY → DEGRADED → RESTART ladder on it.  Strategies without a
    # quorum grad-sync cannot form a step minus a pod, so any masked
    # pod escalates straight to RESTART (can_degrade=False).
    num_pods = mesh.devices.shape[_outer_axis(mesh)]
    needs_mask = bool(getattr(step, "needs_quorum_mask", False))
    watch = Watchdog(num_pods) if (plan or needs_mask) else None
    health = HealthMonitor(num_pods,
                           staleness_limit=args.quorum_staleness,
                           can_degrade=needs_mask) if watch else None

    dspec = P(ba)
    in_specs = [st.pspecs, st.ospecs, dspec, dspec, None]
    if needs_mask:
        in_specs.append(P())           # quorum mask: replicated
    step_fn = jax.jit(
        jax.shard_map(step, mesh=mesh,
                      in_specs=tuple(in_specs),
                      out_specs=(P(), st.pspecs, st.ospecs),
                      check_vma=False),
        donate_argnums=(0, 1))

    loader = make_loader(cfg, args.seq, args.batch, seed=args.seed)

    # SIGTERM (preemption) → emergency checkpoint at the next step boundary
    terminate = {"now": False}
    old = signal.signal(signal.SIGTERM,
                        lambda *_: terminate.__setitem__("now", True))

    t0 = time.time()
    losses = []
    done = start_step        # last COMPLETED step count (emergency save)
    saved = start_step       # largest step known committed
    restart_lost = None      # set when the health ladder demands RESTART
    try:
        for s in range(start_step, args.steps):
            mask = None
            if watch is not None:
                for pod in set(range(num_pods)) \
                        - set(plan.pods_down(s, num_pods)):
                    watch.heartbeat(pod, s)
                mask = watch.mask(s)
                state = health.observe(s, mask)
                if state == RESTART:
                    restart_lost = _restart_flat_indices(
                        mesh0, lost, health.restart_pods())
                    break
                if state == DEGRADED:
                    rows = args.batch // num_pods
                    for pod in watch.stale(s):
                        # the dropped rows are a pure function of
                        # (seed, step, row range) — ShardedLoader
                        # .batch_slice regenerates exactly them
                        print(f"degraded step {s}: pod {pod} masked; "
                              f"rows [{pod * rows}, {(pod + 1) * rows})"
                              f" dropped, replayable from (seed="
                              f"{args.seed}, step={s})", flush=True)
            toks, labels = loader.batch_at(s)
            call = [params, opt_state, jnp.asarray(toks),
                    jnp.asarray(labels), None]
            if needs_mask:
                call.append(jnp.asarray(
                    mask if mask is not None
                    else np.ones((num_pods,), np.float32)))
            loss, params, opt_state = step_fn(*call)
            done = s + 1     # only after the step returned — a raise or
            #                  SIGTERM mid-step must not claim step s
            if s % args.log_every == 0 or s == args.steps - 1:
                lv = float(loss)
                losses.append(lv)
                dt = time.time() - t0
                tps = (s - start_step + 1) * args.batch * args.seq / dt
                print(f"step {s:5d}  loss {lv:8.4f}  tok/s {tps:9.0f}",
                      flush=True)
            if ckpt and done % args.ckpt_every == 0:
                ckpt.save(done, (params, opt_state),
                          attempt_hook=plan.ckpt_attempt_hook(done))
                saved = done
                _post_commit_faults(ckpt, plan, args.ckpt, done)
            if terminate["now"]:
                print("SIGTERM: emergency checkpoint")
                break
    finally:
        signal.signal(signal.SIGTERM, old)
        # whether the loop is already unwinding an exception MUST be read
        # before the except below makes it the "current" exception
        unwinding = sys.exc_info()[1] is not None
        if ckpt:
            try:
                if done > saved and _tree_alive((params, opt_state)):
                    ckpt.save(done, (params, opt_state),
                              attempt_hook=plan.ckpt_attempt_hook(done))
                    saved = done
                    _post_commit_faults(ckpt, plan, args.ckpt, done)
                elif done > saved:
                    # a raise INSIDE step done+1 deleted the state (it was
                    # donated into the failing call): nothing to save —
                    # say so instead of crashing on dead buffers
                    print(f"emergency checkpoint skipped: state of step "
                          f"{done} was donated into the failing step; "
                          f"latest committed checkpoint is step {saved}",
                          file=sys.stderr, flush=True)
                ckpt.wait()
            except BaseException as e:  # noqa: BLE001
                # surface the writer failure; only re-raise when it would
                # not mask the exception already unwinding the loop
                print(f"CHECKPOINT ERROR: save at step {done} failed: "
                      f"{e!r}", file=sys.stderr, flush=True)
                if not unwinding:
                    raise
    _commit_tuner_misses(args, tuner)
    if restart_lost is not None:
        print(f"RESTART at step {done}: emergency checkpoint committed, "
              f"shrinking around pods {health.restart_pods()}", flush=True)
        if not args.ckpt:
            print("WARNING: no --ckpt; the restarted attempt re-inits "
                  "from scratch", file=sys.stderr, flush=True)
        return None, restart_lost
    if start_step >= args.steps:
        # resuming a finished run: the loop never ran — nothing to
        # report (and nothing was checkpointed above)
        print(f"nothing to do: resumed at step {start_step} >= "
              f"--steps {args.steps}")
        return 0, None
    if not losses:
        # stopped (SIGTERM) before the first log boundary — real work
        # may still have been checkpointed above
        print(f"stopped at step {done} before the first log boundary")
        return 0, None
    if len(losses) >= 2 and losses[-1] >= losses[0]:
        print(f"WARNING: loss did not decrease ({losses[0]:.3f} → "
              f"{losses[-1]:.3f})")
    else:
        print(f"loss {losses[0]:.4f} → {losses[-1]:.4f}  OK")
    return 0, None


if __name__ == "__main__":
    sys.exit(main())
