#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate once, on the chip, to find its knee.

    python3 chipbench/knee_sweep.py --workload danube_serve_chat \
        --rates 2,4,6,8,10 --seconds 20 --seed 7

One process, one set-up: for each rate a fresh engine over the same
compiled step serves the cell's mix, open loop, for ``--seconds``.  Per
rate it prints the requests due, the queue of requests waiting for a slot
(mean over each third of the window, and at the close), the least-squares
slope of that queue over the window, and TTFT and inter-token gaps.  The
knee is the highest rate at which the waiting queue does not grow over
the window; the cell's rate is set from it by hand and kept in the cell
file as a number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def queue_stats(samples, seconds: float) -> dict:
    """Mean waiting per third of the window, the last value, the slope."""
    if not samples:
        return {}
    t = np.array([s for s, _ in samples])
    q = np.array([n for _, n in samples], float)
    thirds = [q[(t >= k * seconds / 3) & (t < (k + 1) * seconds / 3)]
              for k in range(3)]
    slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 1 else 0.0
    return {"mean_by_third": [float(x.mean()) if len(x) else 0.0
                              for x in thirds],
            "at_close": int(q[-1]), "slope_per_s": slope}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from chipbench import harness as H
    from chipbench.kinds import serve
    cell = H.find_cell(H.load_benchmark(), args.workload)
    H.device_or_exit(cell.chips)
    H.enable_cache()
    t = cell.traffic
    engine = serve.build(cell, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs = serve.make_requests(cell, rate, args.seconds, args.seed)
        win = serve.Window(engine(), t["slots"], H.Spans(False))
        win.run(reqs, time.perf_counter(), args.seconds, 0.0)
        ttft = sorted(win.ttft.values())
        gaps = sorted(win.gaps)
        pct = lambda v, q: (1e3 * v[min(int(q * len(v)), len(v) - 1)]
                            if v else None)
        print(json.dumps({
            "rate_per_s": rate, "due": len(reqs),
            "first_tokens_in_window": len(win.prefill_lens),
            "decodes_per_s": len(win.decode_lens) / args.seconds,
            "waiting": queue_stats(win.queue_len, args.seconds),
            "ttft_ms_p50_p95": [pct(ttft, 0.5), pct(ttft, 0.95)],
            "itl_ms_p50_p95": [pct(gaps, 0.5), pct(gaps, 0.95)]}),
            flush=True)
        del win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
