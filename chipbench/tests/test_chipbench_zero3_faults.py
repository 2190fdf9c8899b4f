"""The four-chip lane_zero3 training cell on four CPU devices at smoke
size, past the look for a chip: a sound run is correct and reports the
collective metrics; a step that returns its state unchanged, half of the
batch left out, and the exchange between chips left out each make
``correct`` false."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def _run(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "zero3_faults.py"), case],
                       cwd=HERE, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    got = _run("sound")
    assert got["correct"], got["checks"]
    assert {"collective_ms.train", "exposed_collective_ms.train"} <= \
        set(got["metrics"])


@pytest.mark.parametrize("case", ["unchanged", "half_batch", "no_exchange"])
def test_fault_is_caught(case):
    got = _run(case)
    assert not got["correct"], got["checks"]
