"""What every cell shares: finding a cell's files by name, the device check,
the compile counter, the per-metric readers and the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

  configs/<config>.json     the model configuration as it is run
  traffic/<traffic>.json    the mix; its ``kind`` names the driver
                            ``kinds/<kind>.py`` that runs it
  cells/<workload>.json     the cell's own parameters (strategy, rate)
  metrics/<metric>.py       one reader per metric: ``read(rec) -> float |
                            None`` over the run's record

A later cell, mix, configuration or metric is a new file and a new entry;
no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Callable, Optional

HERE = Path(__file__).resolve().parent          # chipbench/
ROOT = HERE.parent                              # the checkout

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoDevice(SystemExit):
    """Raised (as a SystemExit, so nothing is printed on stdout) when the
    chips JAX finds do not match what the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    params: dict
    end_to_end: list
    per_layer: list

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic, parameters and the metrics it reports; raises KeyError for a
    name that is not there."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    confs = {c["name"]: c["file"] for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return _cell(name, w, root / confs[w["config"]], e2e, layer)


def candidate_cell(name: str) -> Cell:
    """A cell kept out of ``BENCHMARK.json`` (PERF.md says why), for
    ``control.py`` and the tests alone: its file ``cells/<name>.json``
    names its configuration, mix and chips under ``candidate``, and it
    reports no metric.  ``run.py`` never finds it."""
    w = _read_json(HERE / "cells" / f"{name}.json")["candidate"]
    return _cell(name, w, HERE / "configs" / f"{w['config']}.json", [], [])


def _cell(name: str, w: dict, config: Path, e2e: list, layer: list) -> Cell:
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=_read_json(config),
                traffic=_read_json(HERE / "traffic" / f"{w['traffic']}.json"),
                params=_read_json(HERE / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(cell: Cell):
    return importlib.import_module(f"chipbench.kinds.{cell.kind}")


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    mod = load_module(HERE / "metrics" / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_"))
    return mod.read


def read_peaks() -> dict:
    return _read_json(HERE / "peaks.json")["devices"]


def peaks(kind: str) -> dict:
    """The peak rates of ``kind`` (a jax ``device_kind``); an unknown kind
    is an error, never a default."""
    table = read_peaks()
    if kind not in table:
        raise KeyError(f"no peak rates for device kind {kind!r} in "
                       f"chipbench/peaks.json (have {sorted(table)})")
    return table[kind]


def device_or_exit(chips: int, devices=None) -> dict:
    """The device record of the result line; exits (no result) unless
    JAX finds exactly ``chips`` TPU chips of a kind with known peaks."""
    if devices is None:
        import jax
        devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoDevice(f"chipbench: JAX found no TPU (platform "
                       f"{d0.platform!r}); nothing was run")
    if len(devices) != chips:
        raise NoDevice(f"chipbench: the cell needs {chips} TPU chips, JAX "
                       f"sees {len(devices)}")
    try:
        peaks(d0.device_kind)
    except KeyError as e:
        raise NoDevice(f"chipbench: {e.args[0]}") from None
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


class Compiles:
    """Backend compile seconds and count, and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def mark(self):
        return self.seconds, self.count, self.hits


def enable_cache() -> Optional[str]:
    """The program's persistent compile cache (``.jax_cache`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program,
    however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Spans:
    """The benchmark's own host spans around its calls into each layer
    (``jax.profiler.TraceAnnotation``), and the profiler around the
    window, in a ``--trace 1`` run; nothing at all otherwise."""

    def __init__(self, on: bool, log_dir: Optional[str] = None):
        self.on = on
        self.log_dir = log_dir
        self.path: Optional[str] = None

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        if self.on:
            import jax
            from chipbench.trace import find_xplane
            jax.profiler.stop_trace()
            self.path = find_xplane(self.log_dir)


def runtime_peak(devices) -> int:
    """The runtime's peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def compiled_footprint(compiled) -> int:
    """Arguments + temporaries + outputs that do not alias an argument."""
    ma = compiled.memory_analysis()
    if ma is None:
        return 0
    return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def judge(checks: dict) -> bool:
    """``checks``: name -> {"value", "limit"}; correct when every value is
    a finite number at or under its limit."""
    return all(isinstance(c["value"], (int, float))
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def print_checks(checks: dict, out=None) -> None:
    out = out or sys.stderr
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=out, flush=True)


def metrics_of(rec: dict, wanted: list) -> dict:
    """Each wanted metric its reader finds something to read for, with
    its unit; a reader that returns None leaves its metric out."""
    out = {}
    for m in wanted:
        v = metric_reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result(*, correct: bool, attempted: int, failed: int, metrics: dict,
           device: dict, checks: dict,
           breakdown: Optional[dict] = None) -> dict:
    """The result object; ``checks`` comes last."""
    res: dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks
    return res
