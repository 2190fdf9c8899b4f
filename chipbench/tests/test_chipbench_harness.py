"""The harness finds every part of a cell by its name, refuses devices it
cannot measure, and BENCHMARK.json keeps to the benchmark's contract."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import harness as H  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = H.load_benchmark()


class FakeDevice:
    def __init__(self, platform="tpu", kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_find_cell_by_name(name):
    cell = H.find_cell(BENCH, name)
    assert cell.chips in (1, 4)
    assert cell.kind in ("train", "serve")
    assert (H.HERE / "kinds" / f"{cell.kind}.py").exists()
    assert cell.config["family"] in ("ssm", "dense")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert name in m["workloads"]
    assert cell.params["limits"]


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        H.find_cell(BENCH, "no_such_cell")


@pytest.mark.parametrize("name", ["mamba2_train_1chip",
                                  "mamba2_train_zero3_4chip"])
def test_kept_out_cell_is_found_only_as_a_candidate(name):
    """A cell kept out of BENCHMARK.json is no workload of the benchmark;
    control.py and the tests load it explicitly, with no metric."""
    with pytest.raises(KeyError):
        H.find_cell(BENCH, name)
    cell = H.candidate_cell(name)
    assert cell.kind == "train" and cell.config_name == "mamba2-780m"
    assert cell.end_to_end == [] and cell.per_layer == []


@pytest.mark.parametrize(
    "name", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(H.metric_reader(name))


def test_device_check_takes_a_known_tpu():
    dev = H.device_or_exit(4, [FakeDevice() for _ in range(4)])
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


@pytest.mark.parametrize("devices", [
    [FakeDevice("cpu", "cpu")],
    [FakeDevice(), FakeDevice()],
    [FakeDevice(kind="TPU v99")],
])
def test_device_check_refuses(devices):
    with pytest.raises(SystemExit):
        H.device_or_exit(1, devices)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        H.peaks("TPU v99")
    assert H.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_run_on_cpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_json_keeps_to_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"]
    assert all(not w.startswith("/") and ".." not in w for w in b["command"])
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [c["name"] for c in b["configs"]]
    used = {w["config"] for w in b["workloads"]}
    assert set(names) == used and len(set(names)) == len(names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert (ROOT / c["file"]).exists()
        assert c["reduced"] == json.loads(
            (ROOT / c["file"]).read_text())["reduced"]
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(json.dumps(b)) < 64 * 1024


def test_run_alone_prints_no_result(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ has no program:
    the run exits non-zero and prints no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
