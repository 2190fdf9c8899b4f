"""Training cells: the program's lane train step, driven as its driver
(``repro.launch.train``) drives it.

Set-up builds one object, the jitted ``shard_map`` step of
``build_train_step_lane`` wrapped and donated as the driver wraps it, with
its master state made on the device from the seed's weights.  It drives
that object through the mix's first ``check_steps`` steps, through the
same call and loader the window uses, and keeps what the comparison
needs: each step's loss, the per-leaf norms of the first gradient as the
optimizer took it (Adam's first moment after one step over 1 - b1), and
the per-leaf norms of the parameters' change over those steps.  The
window then goes on from the next step: steps are dispatched with at most
``in_flight`` in flight and no other host wait, and it closes when the
last step dispatched before ``--seconds`` ran out has finished.  Once the
state is freed, the reference follows the same steps from the same seed.
"""
from __future__ import annotations

import collections
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import checks as C
from chipbench import harness as H
from chipbench import leaves, weights
from chipbench.shapes import train_flops_per_token


def _state_init(cfg, run, mesh, comm, config):
    """``(st, init)`` as the driver's ``launch/train.py:_lane_state`` makes
    them, with the benchmark's weights in place of ``init_model``'s: one
    jit from the seed's key whose outputs carry the mesh shardings."""
    from repro.launch.mesh import batch_axes
    from repro.launch.steps import LaneTrainState, init_lane_train_state
    n, N = comm.topo.sizes(mesh)
    by_layer = (comm.param_layout(run.gradsync) == "zero3"
                and cfg.num_layers % (n * N) == 0)
    layers = NamedSharding(Mesh(mesh.devices, mesh.axis_names),
                           P(batch_axes(mesh)))
    make = weights.make_fn(config)
    static = {}

    def arrays(key):
        params = make(key)
        if by_layer:
            params["blocks"] = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, layers),
                params["blocks"])
        st = init_lane_train_state(cfg, run, mesh, params, comm=comm)
        static["layout"] = (st.pspecs, st.ospecs, st.ckpt_layout)
        return st.params, st.opt_state

    shapes = jax.eval_shape(arrays, weights.seed_key(0))
    st = LaneTrainState(*shapes, *static["layout"])
    return st, jax.jit(arrays, out_shardings=st.to_shardings(mesh))


def _canonical(cfg, layout: str, mesh):
    """``(params_tree, moment_tree)``: the program's master state as the
    canonical parameter tree (float32 where the masters are).  ZeRO-3
    masters are gathered whole first (outside the jitted reduction, as a
    plain ``device_put``)."""
    if layout == "replicated":
        return (lambda p: p), (lambda o: o["m"])
    from repro.launch.steps import zero3_stack_layouts
    lays = zero3_stack_layouts(cfg)
    lb, le = lays["blocks"], lays["extras"]
    whole = NamedSharding(mesh, P())

    @jax.jit
    def tree(blocks, extras, rest):
        out = dict(rest)
        out.update(le.unflatten(extras.reshape(1, -1)[:, :le.row_elems],
                                dtype=jnp.float32))
        out["blocks"] = lb.unflatten(
            blocks.reshape(lb.length, -1)[:, :lb.row_elems],
            dtype=jnp.float32)
        return out

    def gathered(blocks, extras, rest):
        return tree(*jax.device_put((blocks, extras, rest), whole))

    def params(p):
        return gathered(p["blocks"], p["extras"],
                        {k: v for k, v in p.items()
                         if k not in ("blocks", "extras")})

    def moments(o):
        return gathered(o["blocks"]["m"], o["extras"]["m"], o["rest"]["m"])
    return params, moments


class Program:
    """The cell's compiled train step and the state it is given."""

    def __init__(self, cell):
        from repro.configs import RunConfig, resolve
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import batch_axes
        from repro.launch.steps import build_train_step_lane
        from repro.launch.train import make_mesh_auto
        from repro.optim import AdamWConfig
        self.cell = cell
        t = cell.traffic
        self.batch, self.seq = t["batch"], t["seq"]
        self.cfg = cfg = resolve(cell.config["registry"],
                                 smoke=bool(cell.config.get(
                                     "registry_smoke")))
        C.config_matches(cfg, cell.config)
        run = RunConfig(model=cfg,
                        shape=ShapeConfig("bench", self.seq, self.batch,
                                          "train"),
                        remat=t["remat"], gradsync=cell.params["gradsync"])
        mesh = make_mesh_auto(self.batch, cell.params.get("pods", 1))
        step, comm = build_train_step_lane(cfg, run,
                                           AdamWConfig(**t["optimizer"]),
                                           mesh, None)
        st, self.init = _state_init(cfg, run, mesh, comm, cell.config)
        dspec = P(batch_axes(mesh))
        self.step_fn = jax.jit(
            jax.shard_map(step, mesh=mesh,
                          in_specs=(st.pspecs, st.ospecs, dspec, dspec,
                                    None),
                          out_specs=(P(), st.pspecs, st.ospecs),
                          check_vma=False),
            donate_argnums=(0, 1))
        self.compiled = None
        ptree, mtree = _canonical(cfg, comm.param_layout(run.gradsync),
                                  mesh)
        b1 = t["optimizer"]["b1"]
        norms = jax.jit(leaves.norms)
        diff = jax.jit(leaves.diff_norms)
        self.grad_norms = lambda o: norms(mtree(o)) / (1 - b1)
        self.change_norms = lambda p, p0: diff(ptree(p), p0)
        self.make = jax.jit(weights.make_fn(cell.config))

    def loader(self, seed: int):
        from repro.data import make_loader
        return make_loader(self.cfg, self.seq, self.batch, seed=seed)

    def call(self, params, opt, loader, s: int):
        toks, labels = loader.batch_at(s)
        toks, labels = jnp.asarray(toks), jnp.asarray(labels)
        if self.compiled is None:
            self.compiled = self.step_fn.lower(params, opt, toks, labels,
                                               None).compile()
        return self.compiled(params, opt, toks, labels, None)

    def first_steps(self, seed: int):
        """``(params, opt, loader, readings)``: the seed's state driven
        through the mix's first steps; readings are the losses and the
        per-leaf norms of the first gradient and of the change."""
        key = weights.seed_key(seed)
        loader = self.loader(seed)
        params, opt = self.init(key)
        losses, grad1 = [], None
        for s in range(self.cell.traffic["check_steps"]):
            loss, params, opt = self.call(params, opt, loader, s)
            losses.append(float(loss))
            if s == 0:
                grad1 = np.asarray(self.grad_norms(opt))
        change = np.asarray(self.change_norms(params, self.make(key)))
        return params, opt, loader, {"losses": losses, "grad1": grad1,
                                     "change": change}


def reference(cell, seed: int, *, mode: str = "f32", rows: str = "all"):
    """The reference's readings of the same first steps."""
    from chipbench.reference import train as ref_train
    return ref_train.follow(cell.config, cell.traffic,
                            cell.params["master"], seed,
                            steps=cell.traffic["check_steps"], mode=mode,
                            rows=rows, devices=jax.devices()[:cell.chips])


def run(cell, seed: int, seconds: float, spans, compiles, devices):
    t = cell.traffic
    prog = Program(cell)
    params, opt, loader, got = prog.first_steps(seed)

    in_flight = max(int(t.get("in_flight", 2)), 1)
    pending = collections.deque()
    window_losses = []
    s = t["check_steps"]
    mark = compiles.mark()
    spans.start()
    with spans("chipbench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with spans("chipbench.feed"):
                toks, labels = loader.batch_at(s)
                toks, labels = jnp.asarray(toks), jnp.asarray(labels)
            with spans("chipbench.train_step"):
                loss, params, opt = prog.compiled(params, opt, toks, labels,
                                                  None)
            pending.append(loss)
            s += 1
            if len(pending) >= in_flight:
                with spans("chipbench.wait"):
                    window_losses.append(float(pending.popleft()))
        with spans("chipbench.wait"):
            while pending:
                window_losses.append(float(pending.popleft()))
        t1 = time.perf_counter()
    spans.stop()
    in_window = compiles.mark()
    steps = s - t["check_steps"]
    rec = {
        "kind": "train", "chips": cell.chips, "window_t0": t0,
        "window_s": t1 - t0, "steps": steps,
        "tokens": steps * prog.batch * prog.seq,
        "flops_per_token": train_flops_per_token(cell.config, prog.seq),
        "compiles_in_window": in_window[1] - mark[1],
        "memory_peak_bytes": H.runtime_peak(devices),
        "memory_compiled_bytes": H.compiled_footprint(prog.compiled),
    }
    finite = all(math.isfinite(x) for x in window_losses)
    print(f"train window: {steps} steps of {prog.batch} x {prog.seq} in "
          f"{rec['window_s']:.4f} s; first-step losses {got['losses']}; "
          f"window losses {window_losses[:2]} ... {window_losses[-2:]}; "
          f"compiles in window {rec['compiles_in_window']}", flush=True)
    print(f"train memory: runtime peak {rec['memory_peak_bytes']} B, "
          f"compiled step footprint {rec['memory_compiled_bytes']} B",
          flush=True)
    del params, opt, prog
    gc.collect()

    ref = reference(cell, seed)
    names = leaves.names(jax.eval_shape(weights.make_fn(cell.config),
                                        weights.seed_key(0)))
    print(f"train check: reference losses {ref['losses']}; worst leaves "
          f"{C.worst_leaves(got, ref, names)}", flush=True)
    rec["checks"] = C.with_limits(
        C.train_numbers(got["losses"], got["grad1"], got["change"], ref),
        cell.params["limits"])
    rec["attempted"] = steps
    rec["failed"] = 0 if finite else 1
    return rec
