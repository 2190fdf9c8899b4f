"""CI leg: the serving tier must actually SERVE, end to end.

Three checks, each a production path rather than a unit:

  * scenario sweep — the registry-derived scenario generator drives the
    continuous batcher over every scenario kind for a bucketed family
    (dense) and an exact-length-prefill family (ssm); every request must
    finish with a recorded reason and a first-token timestamp;
  * checkpoint → serve — a REAL training-driver checkpoint (2 steps,
    native gradsync → replicated layout) restored through
    ``load_serve_params`` must serve a scenario to completion, proving
    the train→serve hand-off path stays wired;
  * zero3 identity — the restored weights served under ``lane_zero3``
    hosting (1/p masters, prefetch-gathered layers, sharded slots,
    kv_splice cache distribution) must produce byte-identical tokens to
    replicated hosting.

The full hosting × family × scenario matrix lives in
``repro.testing.serve_cases`` (run by tier1); this leg is the fast
always-on heartbeat that names a red serving path even when tier1 dies
earlier.

Usage:  python -m repro.serve.serve_smoke   (wired into ``make ci``)
"""
import sys
import tempfile


def _run_scenarios(cfg, params, kinds, *, slots):
    from repro.serve import ContinuousBatcher, make_scenario
    for kind in kinds:
        reqs = make_scenario(cfg, kind=kind, n=5, seed=3, max_seq=96)
        eng = ContinuousBatcher(params, cfg, slots=slots, max_seq=96)
        done, stats = eng.run(reqs)
        if len(done) != len(reqs):
            raise RuntimeError(f"{kind}: {len(done)}/{len(reqs)} done")
        if stats["decode_tokens"] <= 0:
            raise RuntimeError(f"{kind}: no decode tokens")
        for r in done:
            if not r.done or r.finish_reason is None:
                raise RuntimeError(f"{kind}: request {r.rid} unfinished")
            if r.t_first is None:
                raise RuntimeError(f"{kind}: request {r.rid} missing "
                                   f"first-token time")
        print(f"  {cfg.family:6s} {kind:13s} "
              f"{stats['decode_tokens']:4d} tok  "
              f"{stats['tok_per_s']:.1f} tok/s", flush=True)


def main(argv=None) -> int:
    import numpy as np
    import jax
    from repro.configs import resolve
    from repro.launch.train import main as train_main
    from repro.models import init_model
    from repro.serve import (ContinuousBatcher, SCENARIO_KINDS,
                             load_serve_params, make_scenario)

    fails = []

    def _leg(name, fn):
        print(f"=== serve-smoke {name} ===", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            fails.append(name)
            print(f"FAIL {name}: {e!r}", flush=True)
        else:
            print(f"PASS {name}", flush=True)

    def _scenarios():
        for arch in ("llama3.2-3b", "mamba2-780m"):
            cfg = resolve(arch, smoke=True)
            params = init_model(jax.random.PRNGKey(0), cfg)
            _run_scenarios(cfg, params, SCENARIO_KINDS, slots=3)

    _leg("scenario_sweep[dense,ssm]", _scenarios)

    def _ckpt_and_zero3():
        cfg = resolve("llama3.2-3b", smoke=True)
        with tempfile.TemporaryDirectory() as td:
            ck = f"{td}/ck"
            rc = train_main(["--arch", "llama3.2-3b", "--smoke",
                             "--batch", "8", "--seq", "32", "--ckpt", ck,
                             "--steps", "2", "--ckpt-every", "2",
                             "--gradsync", "native", "--pods", "2"])
            if rc != 0:
                raise RuntimeError(f"training run failed: rc={rc}")
            params, step = load_serve_params(ck, cfg)
            if step != 2:
                raise RuntimeError(f"loaded step {step}, expected 2")
        reqs = lambda: make_scenario(cfg, kind="short_chat", n=6,  # noqa: E731
                                     seed=7, max_seq=96)
        rep = ContinuousBatcher(params, cfg, slots=2, max_seq=96)
        rep_done, _ = rep.run(reqs())
        if not all(r.done for r in rep_done):
            raise RuntimeError("replicated engine left requests undone")
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(2, 2, 2),
            ("pod", "data", "model"))
        z3 = ContinuousBatcher(params, cfg, slots=8, max_seq=96,
                               hosting="lane_zero3", mesh=mesh)
        z3_done, z3_stats = z3.run(reqs())
        if z3_stats["hosting"] != "lane_zero3":
            raise RuntimeError(f"hosting {z3_stats['hosting']!r}, "
                               f"expected lane_zero3")
        a = {r.rid: r.out for r in rep_done}
        b = {r.rid: r.out for r in z3_done}
        if a != b:
            raise RuntimeError(
                f"zero3 ≠ replicated: "
                f"{ {k: (a[k], b[k]) for k in a if a[k] != b[k]} }")
        print(f"  ckpt step {step} → replicated == lane_zero3 over "
              f"{len(a)} requests", flush=True)

    _leg("ckpt_to_serve_zero3_identity[dense]", _ckpt_and_zero3)

    print(f"serve-smoke: {2 - len(fails)}/2 legs OK"
          + (f"; FAILED {fails}" if fails else ""))
    return len(fails)


if __name__ == "__main__":
    # before the first jax import (main imports it): 8 CPU devices
    from repro.tuning.backend import apply_backend_setup
    apply_backend_setup("cpu", host_device_count=8)
    sys.exit(main(sys.argv[1:]))
