"""Cells of the benchmark cut to the registry's smoke sizes, for the CPU
tests: the same files and limits, a tiny configuration and mix."""
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness as H  # noqa: E402

SMOKE = {"mamba2-780m": "mamba2-smoke", "h2o-danube-3-4b": "danube-smoke"}


def cell(name: str, candidate: bool = False):
    """``name`` from BENCHMARK.json, or with ``candidate`` a cell kept out
    of it (``harness.candidate_cell``)."""
    c = (H.candidate_cell(name) if candidate
         else H.find_cell(H.load_benchmark(), name))
    c.config = json.loads((HERE / "data" / f"{SMOKE[c.config_name]}.json")
                          .read_text())
    if c.kind == "train":
        c.traffic = dict(c.traffic, batch=4 * c.chips, seq=64)
    else:
        c.traffic = dict(
            c.traffic, slots=4, max_seq=64, buckets=[8, 16],
            prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 12},
            output={"median": 6, "sigma": 0.5, "min": 3, "max": 8},
            drain_s=20, check_tokens=30)
        c.params = dict(c.params, rate_per_s=20.0)
    return c


# the metrics a training cell reads (training cells are kept out of
# BENCHMARK.json for now, see PERF.md, so none is listed there)
TRAIN_METRICS = {
    "end_to_end": ["train_tok_s", "peak_hbm_gib", "setup_s"],
    "per_layer": ["idle_share.train", "mfu.train", "collective_ms.train",
                  "exposed_collective_ms.train"]}


def with_train_metrics(c):
    c.end_to_end = [{"name": n, "unit": "-"}
                    for n in TRAIN_METRICS["end_to_end"]]
    c.per_layer = [{"name": n, "unit": "-"}
                   for n in TRAIN_METRICS["per_layer"]]
    return c


def run_cell(c, seed: int = 2 ** 33 + 7, seconds: float = 2.0,
             trace: bool = False) -> dict:
    """A whole run of ``c`` on the CPU, past the look for a chip."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", ROOT / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_cell(c, seed=seed, seconds=seconds, trace=trace,
                        check_device=False)
