"""The harness on the CPU at tiny sizes: the contract's last line, the
numbers compared on standard error, and no result off the chip."""
import json

import pytest

from .cells import REPO, SEED, TINY, bench_copy, run_harness, run_python

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_copy(tmp_path_factory.mktemp("bench"))


def _cell_metrics(kind: str, workload: str):
    return {m["name"] for m in SPEC[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_run_prints_the_contract_line(root, workload):
    proc, line = run_harness(root, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == _cell_metrics("end_to_end", workload)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    err = proc.stderr.strip().splitlines()
    n = len(line["compared"])
    assert n >= 2 and err[-n - 1] == "correct True"
    for ln, (name, c) in zip(err[-n:], line["compared"].items()):
        assert ln == f"compared {name} = {c['value']!r} (limit " \
                     f"{c['limit']!r})"


@pytest.mark.parametrize("workload", sorted(TINY))
def test_traced_run_reads_the_cells_layers(root, workload):
    _, line = run_harness(root, workload, trace=True)
    want = _cell_metrics("per_layer", workload)
    # no device plane in a CPU trace, so no idle share; the rest is read
    assert set(line["metrics"]) == {m for m in want
                                    if not m.startswith("device_idle")}
    compiles = [v["value"] for k, v in line["metrics"].items()
                if k.startswith("window_compiles")]
    assert compiles == [0.0]


def test_off_the_chip_exits_nonzero_without_a_result(root):
    proc = run_python(root, ["bench/run.py", "--workload",
                             "boutique.eager-tick", "--seed", str(SEED),
                             "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    root = bench_copy(tmp_path)
    proc = run_python(root, ["bench/run.py", "--workload",
                             "boutique.eager-tick", "--seed", str(SEED),
                             "--seconds", "1", "--trace", "0"],
                      with_program=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
