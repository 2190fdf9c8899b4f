"""CI leg: the training driver must actually RUN every registered
gradsync strategy, with a save → restore round-trip.

For each strategy in the ``train_step`` registry (derived, never
hard-coded — a new registration is automatically covered, a lost one
fails the schema checks instead) this drives
``repro.launch.train --smoke`` twice on the 8-device multi-pod CPU mesh:
a fresh 2-step run that commits a checkpoint, then a resumed 3-step run
that must restore it (the driver prints ``resumed from step 2``; a
restore failure raises).  A strategy the driver cannot serve — missing
layout registration, broken state init, un-restorable checkpoint —
fails the build here rather than surviving as a benchmark-only artifact.

The ``lane_zero3`` strategy additionally sweeps the model FAMILIES
(dense/transformer, ssm, hybrid, moe — the driver-trainable subset of
the block-stack registry): the sharded stack is family-agnostic now,
and a family whose registered BlockSpec cannot actually train + restore
through the driver fails the build here too.

Usage:  python -m repro.launch.train_smoke   (wired into ``make ci``)
"""
import sys
import tempfile

def main(argv=None) -> int:
    from repro.checkpoint import latest_step
    from repro.comm import strategies_for
    from repro.launch.train import main as train_main
    from repro.models.blockstack import family_smoke_archs
    import repro.launch.steps  # noqa: F401 - registers train_step table

    # the zero3 family sweep DERIVES from the block-stack registry (the
    # driver-trainable subset: vlm/audio declare needs_extra_embeds and
    # are covered by the conformance grid instead) — a newly registered
    # family joins the sweep without an edit here
    sweep_archs = family_smoke_archs(driver_trainable_only=True)

    strategies = strategies_for("train_step")
    cells = []
    for s in strategies:
        if s == "lane_zero3":
            cells += [(s, fam, arch) for fam, arch in sweep_archs.items()]
        else:
            cells.append((s, "dense", "llama3.2-3b"))

    fails = []
    for s, fam, arch in cells:
        name = f"{s}[{fam}]" if s == "lane_zero3" else s
        print(f"=== train-smoke {name} ===", flush=True)
        try:
            with tempfile.TemporaryDirectory() as td:
                ck = f"{td}/ck"
                base = ["--arch", arch, "--smoke", "--batch", "8",
                        "--seq", "32", "--ckpt", ck, "--ckpt-every", "2",
                        "--log-every", "1", "--gradsync", s, "--pods", "2"]
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
    print(f"train-smoke: {len(cells) - len(fails)}/{len(cells)} "
          f"cells OK" + (f"; FAILED {fails}" if fails else ""))
    return len(fails)


if __name__ == "__main__":
    # before the first jax import (main imports it): 8 CPU devices
    from repro.tuning.backend import apply_backend_setup
    apply_backend_setup("cpu", host_device_count=8)
    sys.exit(main(sys.argv[1:]))
