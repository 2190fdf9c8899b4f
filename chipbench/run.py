#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, parameters and metrics are
found by the names in ``BENCHMARK.json`` (see ``chipbench/harness.py``).
Set-up (weights from the seed, compilation, warm-up, the first steps the
comparison reads) counts as ``setup_s``; then the cell measures for
``--seconds``.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result holds its per-layer metrics, the device's busy and window seconds,
and a breakdown.  Either way the run ends by comparing what the timed
path produced with the plain reference: each number compared is printed
beside its limit on the last lines of standard error, and under
``checks`` at the end of the result.

The last line of standard output is the result, a JSON object.  Where JAX
finds no TPU, or not as many chips as the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             check_device: bool = True, t0: float = T0) -> dict:
    """Run ``cell``; returns the result object.  ``check_device=False``
    skips the look for TPU chips (the CPU tests drive a run with it)."""
    import jax
    from chipbench import harness as H
    from chipbench import trace as T
    devices = jax.devices()
    if check_device:
        device = H.device_or_exit(cell.chips, devices)
        peak = H.peaks(device["kind"])
    else:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        peak = next(iter(H.read_peaks().values()))
    H.enable_cache()
    compiles = H.Compiles()
    kind = H.kind_module(cell)
    with tempfile.TemporaryDirectory() as log_dir:
        spans = H.Spans(trace, log_dir)
        rec = kind.run(cell, seed, seconds, spans, compiles, devices)
        rec["peak"] = peak
        rec["setup_s"] = rec["window_t0"] - t0
        if trace:
            rec["trace"] = T.reduce(spans.path,
                                    host_ops_as_chip=not check_device)
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    breakdown = None
    if trace:
        device["busy_s"] = T.busy_s(rec["trace"])
        device["window_s"] = rec["trace"].window_s
        breakdown = T.breakdown(rec["trace"])
    wanted = cell.per_layer if trace else cell.end_to_end
    checks = rec["checks"]
    correct = H.judge(checks) and rec["failed"] == 0
    H.print_checks(checks)
    return H.result(correct=correct, attempted=rec["attempted"],
                    failed=rec["failed"],
                    metrics=H.metrics_of(rec, wanted), device=device,
                    checks=checks, breakdown=breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import json
    from chipbench import harness as H
    cell = H.find_cell(H.load_benchmark(), args.workload)
    res = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
