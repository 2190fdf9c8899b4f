"""End-to-end behaviour: dry-run artifacts are complete and healthy, the
roofline inputs exist, and the production mesh constructors behave."""
import json
import math
import pathlib

import pytest

from repro.configs import all_archs, resolve, cells

RUNS = pathlib.Path(__file__).resolve().parents[1] / "runs" / "dryrun"

HBM_BYTES = 16e9          # TPU v5e per chip


def _cells(mesh):
    out = []
    for a in all_archs():
        for s in cells(a):
            out.append((a, s, mesh))
    return out


def _load(arch, shape, mesh):
    p = RUNS / mesh / f"{arch}__{shape}.json"
    if not p.exists():
        pytest.skip(f"dry-run artifact missing: {p} (run dryrun --all)")
    return json.loads(p.read_text())


@pytest.mark.parametrize("arch,shape,mesh",
                         _cells("single") + _cells("multi"))
def test_dryrun_cell_compiled(arch, shape, mesh):
    r = _load(arch, shape, mesh)
    assert r["chips"] == (512 if mesh == "multi" else 256)
    assert "error" not in r["memory_analysis"], r["memory_analysis"]
    assert r["hlo_stats"]["flops"] > 0
    assert r["collectives"]["total_wire_bytes"] > 0


@pytest.mark.parametrize("arch,shape,mesh",
                         _cells("single") + _cells("multi"))
def test_dryrun_cell_fits_hbm(arch, shape, mesh):
    r = _load(arch, shape, mesh)
    m = r["memory_analysis"]
    live = m["argument_size_in_bytes"] + m["temp_size_in_bytes"]
    # TPU-adjusted: XLA:CPU keeps fp32 mirrors of large bf16 buffers for
    # its dot lowering (quantified per cell by the dry-run); the TPU MXU
    # consumes bf16 directly so those buffers don't exist there.
    live -= r.get("f32_mirror_bytes", 0)
    # 10% tolerance: CPU buffer assignment takes no donation-alias credit
    assert live <= HBM_BYTES * 1.10, f"{live/1e9:.1f} GB adj"


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_complete(mesh):
    if not (RUNS / mesh).exists():
        # same degradation as _load: artifacts are produced by the (hours-
        # long) dryrun --all sweep, not shipped with the repo
        pytest.skip(f"no dry-run artifacts at {RUNS / mesh} "
                    f"(run dryrun --all)")
    want = {(a, s) for a in all_archs() for s in cells(a)}
    have = {tuple(p.stem.split("__")) for p in (RUNS / mesh).glob("*.json")}
    missing = want - have
    assert not missing, f"missing {mesh} cells: {sorted(missing)[:5]}"


def test_multi_pod_cells_cross_dcn():
    """The pod axis must actually be exercised: multi-pod train cells
    show nonzero DCN wire bytes (the cross-pod gradient reduction)."""
    for a in all_archs():
        r = _load(a, "train_4k", "multi")
        assert r["hlo_stats"]["dcn_wire"] > 0, a


def test_long500k_skips_documented():
    for a in all_archs():
        cfg = resolve(a)
        if not cfg.subquadratic:
            assert "long_500k" not in cells(a)
    # and the ones that run, ran
    for a in ("mamba2-780m", "zamba2-7b", "h2o-danube-3-4b"):
        _load(a, "long_500k", "single")


def test_production_mesh_requires_512_devices():
    import jax
    from repro.launch.mesh import make_production_mesh
    if len(jax.devices()) >= 512:
        m = make_production_mesh(multi_pod=True)
        assert m.devices.shape == (2, 16, 16)
    else:
        with pytest.raises(Exception):
            make_production_mesh(multi_pod=True)


_SEQ_SHARDED_DECODE = """
import sys
import numpy as np
import jax
from repro.launch import dryrun
from repro.configs import resolve, ShapeConfig
cfg = resolve(sys.argv[1], smoke=True)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                         ("data", "model"))
shape = ShapeConfig("decode_seq_sharded", int(sys.argv[2]),
                    int(sys.argv[3]), "decode")
lowered, _ = dryrun.lower_cell(cfg, shape, mesh)
print(lowered.compile().as_text())
"""


def test_dryrun_decode_with_sequence_sharded_cache_keeps_the_select():
    """A decode lowered as the dry-run lowers it, with each layer's cache
    sharded (batch over "data", S over "model", 8 CPU devices): the cache
    write is the one-hot select over each chip's own layer slice, and no
    all-gather brings the cache together (a write at a traced position
    would make GSPMD gather it)."""
    import os
    import re
    import subprocess
    import sys
    arch, S, B = "llama3.2-3b", 256, 8
    cfg = resolve(arch, smoke=True)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.pathsep.join(
               p for p in (src, os.environ.get("PYTHONPATH")) if p)}
    proc = subprocess.run(
        [sys.executable, "-c", _SEQ_SHARDED_DECODE, arch, str(S), str(B)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    text = proc.stdout
    local = f"[{B // 2},{S // 4},{cfg.num_kv_heads},{cfg.hd()}]"
    selects = [line for line in text.splitlines()
               if re.search(r"= \w+" + re.escape(local) + r"\S* select\(",
                            line) and "/attn/kv_write/" in line]
    assert selects, "no one-hot select over a chip's layer slice"
    slice_elems = (B // 2) * (S // 4) * cfg.num_kv_heads * cfg.hd()
    gathered = [m.group(1) for m in re.finditer(
        r"= \w+\[([\d,]*)\]\S* all-gather\(", text)
        if math.prod(int(d) for d in m.group(1).split(",") if d)
        >= slice_elems]
    assert not gathered, gathered
