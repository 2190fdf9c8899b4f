#!/usr/bin/env python3
"""Read the numbers that set a cell's limits, on the chip, in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 15]

For every seed of ``--seeds`` the program's own readings (what a sound
run of the timed path compares); for every seed of ``--control-seeds``
also the control's, the reference computed in the precision below the
configuration's (fp8 for bf16) put in the program's place, and for a
training cell the fault of half the batch left out (the reference's loss
over half of each batch) and a witness: the reference with its matrix
products' operands rounded to bf16, the rounding a sound bf16 program
cannot avoid.  A training step that returns its state
unchanged reads 1 by the change measure and needs no run.  One JSON line
per seed on standard output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def train_readings(cell, seeds, control_seeds):
    import jax
    from chipbench import checks as C
    from chipbench import leaves, weights
    from chipbench.kinds import train
    prog = train.Program(cell)
    names = leaves.names(jax.eval_shape(weights.make_fn(cell.config),
                                        weights.seed_key(0)))
    for seed in seeds:
        params, opt, _, got = prog.first_steps(seed)
        del params, opt
        gc.collect()
        ref = train.reference(cell, seed)
        out = {"seed": seed, "program": C.train_numbers(
            got["losses"], got["grad1"], got["change"], ref),
            "losses": got["losses"], "ref_losses": ref["losses"],
            "worst": C.worst_leaves(got, ref, names)}
        if seed in control_seeds:
            for name, kw in (("control", {"mode": "fp8"}),
                             ("half_batch", {"rows": "half"}),
                             ("bf16_products", {"mode": "bf16"})):
                r = train.reference(cell, seed, **kw)
                out[name] = C.train_numbers(r["losses"], r["grad1"],
                                            r["change"], ref)
                out[name + "_worst"] = C.worst_leaves(r, ref, names)
        print(json.dumps(dict(out, correct=_judged(cell, out))), flush=True)


def serve_readings(cell, seeds, control_seeds, seconds: float):
    import time
    from chipbench import harness as H
    from chipbench.kinds import serve
    t = cell.traffic
    rate = float(cell.params["rate_per_s"])
    for seed in seeds:
        engine = serve.build(cell, seed)
        reqs = serve.make_requests(cell, rate, seconds, seed)
        win = serve.Window(engine(), t["slots"], H.Spans(False))
        win.run(reqs, time.perf_counter(), seconds, t["drain_s"])
        done = win.done
        del engine, win
        gc.collect()
        picked = serve.sample(done, t["check_tokens"], seed)
        modes = ("f32", "fp8") if seed in control_seeds else ("f32",)
        gaps = serve.reference_gaps(cell, seed, picked, modes=modes)
        out = {"seed": seed, "requests": len(picked),
               "tokens": sum(len(r.out) for r in picked),
               "program": {"logit_gap": max(g for g, _ in gaps)}}
        if "fp8" in modes:
            out["control"] = {"logit_gap": max(o["fp8"] for _, o in gaps)}
        print(json.dumps(dict(out, correct=_judged(cell, out))), flush=True)


def _judged(cell, out: dict) -> dict:
    """Whether each side's numbers pass the cell's committed limits by the
    harness's own comparison: the program should, the control not."""
    from chipbench import checks as C
    from chipbench import harness as H
    return {side: H.judge(C.with_limits(out[side], cell.params["limits"]))
            for side in ("program", "control", "half_batch") if side in out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--candidate", action="store_true",
                    help="the cell is kept out of BENCHMARK.json "
                         "(harness.candidate_cell)")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0,
                    help="serving: length of each seed's short window")
    args = ap.parse_args(argv)
    from chipbench import harness as H
    cell = (H.candidate_cell(args.workload) if args.candidate
            else H.find_cell(H.load_benchmark(), args.workload))
    H.device_or_exit(cell.chips)
    H.enable_cache()
    seeds = _seeds(args.seeds)
    control = set(_seeds(args.control_seeds))
    seeds += [s for s in sorted(control) if s not in seeds]
    if cell.kind == "train":
        train_readings(cell, seeds, control)
    else:
        serve_readings(cell, seeds, control, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
