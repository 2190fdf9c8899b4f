"""CI leg: the THIRD parallelism axis through the real training driver.

Sweeps ``--model-parallel`` (tensor parallelism over the mesh's 'model'
axis — mlp_tp activation collectives through registry cells) and
``--expert-parallel`` (MoE token routing as the decomposed moe_route
alltoall, never-gathered (L, E/p) expert master) over a dense and a MoE
arch, each cell a fresh 2-step run committing a checkpoint plus a
resumed run that must restore it — a third-axis configuration the driver
cannot actually train + checkpoint + restore fails the build here.  The
EP cell also sweeps ``--ep-blocks 2`` (the §5 capacity-pipelined routing
whose alltoall/FFN overlap is HLO-pinned in collective_cases).

The bit-identity of these runs against their TP=1 / gather-MoE baselines
is pinned in testing/collective_cases.py and testing/driver_cases.py;
this leg certifies the DRIVER surface end to end.

Usage:  python -m repro.launch.tp_smoke   (wired into ``make ci``)
"""
import sys
import tempfile


# (name, arch, gradsync, extra args) — TP over dense for both replicated
# and zero3 step flavors; EP over the MoE smoke arch (lane_zero3 is the
# flavor with the never-gathered expert master; 'lane' slices experts
# from the replicated tree); EP with the pipelined routing depth
CELLS = [
    ("tp2_lane[dense]", "llama3.2-3b", "lane", ["--model-parallel", "2"]),
    ("tp2_zero3[dense]", "llama3.2-3b", "lane_zero3",
     ["--model-parallel", "2"]),
    ("ep_lane[moe]", "dbrx-132b", "lane", ["--expert-parallel"]),
    ("ep_zero3[moe]", "dbrx-132b", "lane_zero3", ["--expert-parallel"]),
    ("ep_zero3_blocks2[moe]", "dbrx-132b", "lane_zero3",
     ["--expert-parallel", "--ep-blocks", "2"]),
]


def main(argv=None) -> int:
    from repro.checkpoint import latest_step
    from repro.launch.train import main as train_main

    fails = []
    for name, arch, gradsync, extra in CELLS:
        print(f"=== tp-smoke {name} ===", flush=True)
        try:
            with tempfile.TemporaryDirectory() as td:
                ck = f"{td}/ck"
                base = ["--arch", arch, "--smoke", "--batch", "8",
                        "--seq", "16", "--ckpt", ck, "--ckpt-every", "2",
                        "--log-every", "1", "--gradsync", gradsync,
                        "--pods", "2", *extra]
                rc = train_main([*base, "--steps", "2"])
                if rc != 0 or latest_step(ck) != 2:
                    raise RuntimeError(
                        f"fresh run failed: rc={rc}, "
                        f"step={latest_step(ck)}")
                rc = train_main([*base, "--steps", "3"])    # restore path
                if rc != 0 or latest_step(ck) != 3:
                    raise RuntimeError(
                        f"restore run failed: rc={rc}, "
                        f"step={latest_step(ck)}")
        except Exception as e:  # noqa: BLE001
            fails.append(name)
            print(f"FAIL {name}: {e!r}", flush=True)
        else:
            print(f"PASS {name}", flush=True)
    print(f"tp-smoke: {len(CELLS) - len(fails)}/{len(CELLS)} cells OK"
          + (f"; FAILED {fails}" if fails else ""))
    return len(fails)


if __name__ == "__main__":
    # before the first jax import (main imports it): 8 CPU devices
    from repro.tuning.backend import apply_backend_setup
    apply_backend_setup("cpu", host_device_count=8)
    sys.exit(main(sys.argv[1:]))
