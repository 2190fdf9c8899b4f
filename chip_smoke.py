"""Run the training driver and the serving engine on a TPU, at full model width.

    python chip_smoke.py             one chip: train mamba2-780m, serve
                                     h2o-danube-3-4b
    python chip_smoke.py --chips 4   four chips: the lane_zero3 training path
                                     (2 pods x 2 data) against native

One chip (the default):

  train   ``repro.launch.train.main`` trains mamba2-780m at its published
          width (48 layers, d_model 1536, vocab 50280) for 5 steps of batch
          4 x 2048 tokens with full remat and native gradient sync.  Every
          logged loss must be finite, and the step-0 loss must agree with a
          float32 forward (matmul precision "highest") of the same weights
          and batch within REF_RTOL.
  serve   a ``ContinuousBatcher`` holds h2o-danube-3-4b at its published
          width (24 layers, d_model 3840) with 4 slots of 2048 positions and
          answers a short-chat scenario twice (cold, then warm).  Every
          request must finish with a reason, and the batched tokens must
          equal the same requests served one at a time, each alone in an
          engine with the same step.

Four chips (``--chips 4``): the driver trains mamba2-780m at the same batch
and seed with ``--gradsync lane_zero3 --pods 2`` and then with ``native``.
Both step-0 losses (same weights, same batch) must agree within Z3_RTOL0 and
both step-1 losses (one update apart) within Z3_RTOL1.  Each chip's peak
memory is printed after each run: under lane_zero3 no chip holds the whole
unsharded state.

Weights are random, drawn from SEED; data is the driver's seeded synthetic
stream.  Every phase runs in this one process, which holds the chips.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  Where JAX finds no TPU, or a phase fails, the script
exits non-zero and prints no result.  The lines before it are smoke notes
(wall times, compile seconds, peak bytes), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TRAIN_ARCH = "mamba2-780m"
TRAIN_STEPS = 5
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
TRAIN_ARGV = ["--arch", TRAIN_ARCH, "--steps", str(TRAIN_STEPS),
              "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
              "--remat", "full", "--log-every", "1", "--seed", str(SEED)]
SERVE_ARCH = "h2o-danube-3-4b"
SERVE_SLOTS, SERVE_MAX_SEQ, SERVE_REQUESTS = 4, 2048, 6

# bf16 keeps 8 significant bits: one rounding is at most 2^-9 (0.2%)
# relative.  The loss is a mean over 8192 token losses whose rounding errors
# mostly cancel; a 4-layer, 512-wide cut of this model measured 6.5e-4 on
# the CPU.  1e-2 leaves room for 48 layers of depth.
REF_RTOL = 1e-2
# lane_zero3 and native run the same bf16 forward over the same weights at
# step 0; only the order of the cross-chip reductions differs.
Z3_RTOL0 = 1e-3
# After one update they differ by design: native rounds its bf16 parameters
# after the update, lane_zero3 keeps f32 masters and casts them for the
# forward.  Later steps are only checked finite: the synthetic stream makes
# the loss swing tenfold between steps (measured at a reduced width on the
# CPU), which amplifies that rounding past any useful bound.
Z3_RTOL1 = 1e-2

STEP_LINE = re.compile(r"^step\s+(\d+)\s+loss\s+(\S+)")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class PhaseFailed(RuntimeError):
    pass


class _Tee(io.TextIOBase):
    """stdout that keeps each line with the time it was printed."""

    def __init__(self, out):
        self.out, self.lines, self._part = out, [], ""

    def write(self, s):
        self.out.write(s)
        now = time.perf_counter()
        *done, self._part = (self._part + s).split("\n")
        self.lines += [(now, line) for line in done]
        return len(s)

    def flush(self):
        self.out.flush()


class _Compiles:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit shows as a short compile)."""

    def __init__(self):
        import jax
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def since(self, mark):
        return self.seconds - mark[0], self.hits - mark[1]

    def mark(self):
        return self.seconds, self.hits


def _device_or_exit(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) != chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peaks() -> list:
    import jax
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def _print_peaks(label: str) -> None:
    gib = ", ".join(f"chip {i}: {b / 2**30:.2f} GiB ({b} B)"
                    for i, b in enumerate(_peaks()))
    print(f"peak bytes after {label}: {gib}", flush=True)


def _train(label: str, argv: list, compiles: _Compiles) -> list:
    """Run the training driver in this process; return its step losses."""
    from repro.launch.train import main as train_main
    tee = _Tee(sys.stdout)
    mark = compiles.mark()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = train_main(TRAIN_ARGV + argv)
    if rc != 0:
        raise PhaseFailed(f"train {label}: the driver exited {rc}")
    steps = [(t, int(m[1]), float(m[2])) for t, line in tee.lines
             if (m := STEP_LINE.match(line))]
    if [s for _, s, _ in steps] != list(range(TRAIN_STEPS)):
        raise PhaseFailed(f"train {label}: logged steps "
                          f"{[s for _, s, _ in steps]}")
    losses = [loss for _, _, loss in steps]
    if not all(math.isfinite(x) for x in losses):
        raise PhaseFailed(f"train {label}: losses {losses}")
    walls = [steps[0][0] - t0] + [b[0] - a[0]
                                  for a, b in zip(steps, steps[1:])]
    secs, hits = compiles.since(mark)
    print(f"train {label}: losses {losses}; step 0 with init and compile "
          f"{walls[0]:.2f} s, steps 1-{TRAIN_STEPS - 1} "
          f"{[round(w, 3) for w in walls[1:]]} s; backend compile "
          f"{secs:.2f} s, {hits} cache hits", flush=True)
    return losses


def _reference_loss() -> float:
    """float32, precision "highest" loss of the driver's step-0 weights
    and batch (same seed, same init, same loader)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import resolve
    from repro.data import make_loader
    from repro.models import init_model, loss_fn
    cfg = resolve(TRAIN_ARCH)
    params = jax.jit(init_model, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks, labels = make_loader(cfg, TRAIN_SEQ, TRAIN_BATCH,
                               seed=SEED).batch_at(0)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, t, l: loss_fn(p, cfg, t, l))(
            params, toks, labels)
    return float(loss)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def train_phase(compiles: _Compiles) -> None:
    losses = _train("native, 1 chip", ["--gradsync", "native"], compiles)
    _print_peaks("train")
    ref = _reference_loss()
    rel = _rel(losses[0], ref)
    print(f"train reference: float32 step-0 loss {ref:.6f}, driver "
          f"{losses[0]:.4f}, relative gap {rel:.2e} (limit {REF_RTOL})",
          flush=True)
    if not rel <= REF_RTOL:
        raise PhaseFailed(f"step-0 loss {losses[0]} vs float32 {ref}")


def _clone(r):
    from repro.serve import Request
    return Request(r.rid, r.prompt, max_new_tokens=r.max_new_tokens,
                   arrival_step=r.arrival_step, extra=r.extra)


def serve_phase(compiles: _Compiles) -> None:
    import jax
    from repro.configs import resolve
    from repro.models import init_model
    from repro.serve import ContinuousBatcher, build_serve_step, make_scenario
    cfg = resolve(SERVE_ARCH)
    params = jax.jit(init_model, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    reqs = make_scenario(cfg, kind="short_chat", n=SERVE_REQUESTS,
                         seed=SEED, max_seq=SERVE_MAX_SEQ)
    step = build_serve_step(cfg, max_seq=SERVE_MAX_SEQ, slots=SERVE_SLOTS)

    def serve(batch):
        mark, t0 = compiles.mark(), time.perf_counter()
        outs = {}
        for group in ([reqs] if batch else [[r] for r in reqs]):
            eng = ContinuousBatcher(params, cfg, slots=SERVE_SLOTS,
                                    max_seq=SERVE_MAX_SEQ, step=step)
            done, stats = eng.run([_clone(r) for r in group])
            for r in done:
                if not r.done or r.finish_reason is None:
                    raise PhaseFailed(f"serve: request {r.rid} unfinished")
                outs[r.rid] = r.out
        secs, hits = compiles.since(mark)
        return outs, time.perf_counter() - t0, secs, hits, stats

    runs = {}
    for name in ("batched cold", "batched warm"):
        outs, wall, secs, hits, stats = serve(batch=True)
        runs[name] = outs
        ttft = [r["ttft_ms"] for r in stats["requests"]]
        print(f"serve {name}: {len(outs)} requests, "
              f"{stats['decode_tokens']} decode tokens in {stats['steps']} "
              f"steps, wall {wall:.2f} s, ttft ms "
              f"{[round(t, 1) for t in ttft]}; backend compile "
              f"{secs:.2f} s, {hits} cache hits", flush=True)
    # one at a time through the same compiled step: each request alone in
    # the engine.  A slots=1 step is another program whose bf16 rounding
    # differs, and random weights leave near-tied logits that it flips.
    seq, wall, secs, hits, _ = serve(batch=False)
    print(f"serve one at a time: wall {wall:.2f} s; backend compile "
          f"{secs:.2f} s, {hits} cache hits", flush=True)
    for name, outs in runs.items():
        if outs != seq:
            bad = {k: (outs[k], seq[k]) for k in seq if outs.get(k) != seq[k]}
            raise PhaseFailed(f"serve {name} != one at a time: {bad}")
    print(f"serve: batched tokens == one at a time for {len(seq)} "
          f"requests: {seq}", flush=True)
    _print_peaks("serve")


def four_chip_phase(compiles: _Compiles) -> None:
    z3 = _train("lane_zero3, 2 pods x 2 data",
                ["--gradsync", "lane_zero3", "--pods", "2"], compiles)
    _print_peaks("lane_zero3")
    native = _train("native, 4 data", ["--gradsync", "native"], compiles)
    _print_peaks("lane_zero3 and native")
    gaps = [_rel(z3[s], native[s]) for s in (0, 1)]
    print(f"lane_zero3 vs native: relative loss gap step 0 {gaps[0]:.2e} "
          f"(limit {Z3_RTOL0}), step 1 {gaps[1]:.2e} (limit {Z3_RTOL1})",
          flush=True)
    if not (gaps[0] <= Z3_RTOL0 and gaps[1] <= Z3_RTOL1):
        raise PhaseFailed(f"lane_zero3 losses {z3} vs native {native}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the training driver and the serving engine once "
                    "on a TPU at full model width.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip lane_zero3 vs native "
                         "training phase")
    args = ap.parse_args(argv)
    device = _device_or_exit(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {device}; compile cache {enable_compile_cache()}",
          flush=True)
    compiles = _Compiles()
    if args.chips == 4:
        four_chip_phase(compiles)
    else:
        train_phase(compiles)
        serve_phase(compiles)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
