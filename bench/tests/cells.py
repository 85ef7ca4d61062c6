"""Helpers of the benchmark's tests: tiny sizes of every cell, and a copy
of the benchmark in a scratch directory where a run may write its
compile cache and where entries can be added."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

# Per cell: overrides of its traffic that a CPU test run can hold.  The
# deployment stays as configured; only the trace length shrinks.
TINY = {
    "boutique.eager-tick": {"traffic": {"max_ticks": 120,
                                        "trace_items": 5}},
    "boutique.replay": {"traffic": {"chunk_ticks": 6, "chunks": 8,
                                    "max_items": 9, "judged_ticks": 1000,
                                    "trace_items": 2}},
}
SEED = 2 ** 31 + 12345   # larger than 32 signed bits, as the driver's are


def bench_copy(dest: Path) -> Path:
    """``bench/`` and ``BENCHMARK.json`` copied under ``dest``."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    return dest


def run_python(root: Path, args, timeout: float = 900,
               with_program: bool = True) -> subprocess.CompletedProcess:
    """``python <args>`` from ``root`` on the CPU, with the program's
    sources importable unless ``with_program`` is false."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    path = [str(root)] + ([str(REPO / "src")] if with_program else [])
    env["PYTHONPATH"] = os.pathsep.join(path)
    return subprocess.run([sys.executable] + list(args), cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


HARNESS = """
import json, sys, time
from bench import harness
spec = harness.load_spec()
for w in spec["workloads"]:
    w["chips"] = 1
case = json.loads(sys.argv[1])
r = harness.run_cell(case["workload"], case["seed"], case["seconds"],
                     case["trace"], t_start=time.perf_counter(),
                     require_tpu=False, overrides=case["overrides"],
                     spec=spec)
harness.report(r)
"""


def run_harness(root: Path, workload: str, trace: bool = False,
                seconds: float = 1.0, overrides=None):
    """One harness run of a cell on the CPU, past the look for a chip;
    returns the process and its last line of output as an object."""
    case = {"workload": workload, "seed": SEED, "seconds": seconds,
            "trace": trace,
            "overrides": TINY.get(workload, {}) if overrides is None
            else overrides}
    proc = run_python(root, ["-c", HARNESS, json.dumps(case)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])
