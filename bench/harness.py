"""One run of one cell: set-up, the measured window, the comparison.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
harness finds ``bench/configs/<file>``, ``bench/traffic/<traffic>.json``
and, for each per-layer metric, ``bench/metrics/<name>.py`` (or, where
that file does not exist, the reader of the name's first dotted part:
``device_idle.py`` reads ``device_idle.tick``).  Adding a cell, a
configuration or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Dict, Mapping, Optional

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The run cannot be measured here: no TPU, or fewer chips than the
    cell asks for."""


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def metric_reader(name: str):
    """The ``read(inputs)`` function of a per-layer metric."""
    d = ROOT / "bench" / "metrics"
    path = d / f"{name}.py"
    if not path.exists():
        path = d / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _in_cell(metric: Mapping, workload: str, e2e_of_cell=()) -> bool:
    """Whether a cell reports ``metric``: the cells its ``workloads``
    lists; else every cell for an end-to-end metric, and for a per-layer
    one every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class Cell:
    """A cell set up for one seed: its entries, devices and driver."""

    def __init__(self, workload: str, seed: int, trace: bool, *,
                 require_tpu: bool = True,
                 overrides: Optional[Mapping] = None,
                 spec: Optional[dict] = None):
        from repro.jax_cache import enable_persistent_cache

        from . import deployment, drivers
        from .compiles import CompileCounter

        overrides = overrides or {}
        self.spec = spec or load_spec()
        self.wl = _entry(self.spec["workloads"], workload, "workload")
        cfg_entry = _entry(self.spec["configs"], self.wl["config"], "config")
        # $JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
        self.cache_dir = enable_persistent_cache(
            report=lambda msg: print(msg, file=sys.stderr))
        self.devices = devices_for(int(self.wl["chips"]), require_tpu)
        self.counter = CompileCounter().install()
        cfg = deployment.load_config(ROOT / cfg_entry["file"],
                                     overrides.get("config"))
        self.mix = json.loads((ROOT / "bench" / "traffic"
                               / f"{self.wl['traffic']}.json").read_text())
        self.mix.update(overrides.get("traffic", {}))
        dep = deployment.build(cfg, seed)
        self.driver = drivers.load(self.mix["driver"])(
            dep, self.mix, seed, self.devices, trace)

    def window(self, seconds: float, traced_items: int = 0):
        """Run timed items for ``seconds`` (at least one); the first
        ``traced_items`` under the profiler.  Returns (window_s, items,
        compiles or cache reads inside the window, the trace capture)."""
        import jax

        from . import devtrace

        driver = self.driver
        label = f"bench.{driver.label}"
        capture = devtrace.Capture(len(self.devices)) if traced_items \
            else nullcontext()
        c0 = self.counter.total()
        w0 = time.perf_counter()
        items = 0

        def more() -> bool:
            return (time.perf_counter() - w0 < seconds
                    and not driver.exhausted)

        with capture:
            while items < traced_items and (items == 0 or more()):
                with jax.profiler.TraceAnnotation(label):
                    driver.step()
                items += 1
        while items == 0 or more():
            driver.step()
            items += 1
        window_s = time.perf_counter() - w0
        return window_s, items, self.counter.total() - c0, capture


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: Optional[Mapping] = None,
             spec: Optional[dict] = None) -> dict:
    """Run one cell and return the contract's result object."""
    cell = Cell(workload, seed, trace, require_tpu=require_tpu,
                overrides=overrides, spec=spec)
    setup_s = time.perf_counter() - t_start
    counter, driver, spec = cell.counter, cell.driver, cell.spec
    setup_compiles, setup_hits = counter.compiles, counter.cache_hits
    traced = int(cell.mix.get("trace_items", 1)) if trace else 0
    window_s, items, window_compiles, capture = cell.window(seconds, traced)
    memory_peak = _memory_peak(cell.devices)
    print(f"# set-up {setup_s} s ({setup_compiles} compiles, {setup_hits} "
          f"cache hits, cache {cell.cache_dir}); window {window_s} s, {items} "
          f"items, {window_compiles} compiles or cache reads",
          file=sys.stderr, flush=True)

    attempted, failed = driver.counts()
    compared = driver.judge(driver.answers())
    correct = all(v <= lim for v, lim in compared.values())

    e2e = {**driver.end_to_end(window_s), "setup_s": setup_s}
    e2e_of_cell = [m["name"] for m in spec["end_to_end"]
                   if _in_cell(m, workload)]
    metrics: Dict[str, dict] = {}
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        summary = capture.read()
        inputs = {"window_compiles": window_compiles, "trace": summary,
                  **driver.layer_inputs()}
        for m in spec["per_layer"]:
            if _in_cell(m, workload, e2e_of_cell):
                v = metric_reader(m["name"])(inputs)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    else:
        for m in spec["end_to_end"]:
            if m["name"] in e2e_of_cell:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    # a non-finite reading is reported as the largest float, which fails
    # any limit and stays valid JSON
    result["compared"] = {
        k: {"value": v if math.isfinite(v) else sys.float_info.max,
            "limit": lim}
        for k, (v, lim) in compared.items()}
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The verdict and then the numbers compared, each beside its limit,
    as the last lines of standard error; the result as the last line of
    standard output."""
    print(f"correct {result['correct']}", file=err, flush=True)
    for k, c in result["compared"].items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result, default=_plain), file=out, flush=True)


def _plain(x):
    """numpy scalars as Python numbers, for ``json.dumps``."""
    return x.item()
