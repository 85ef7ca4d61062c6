"""Configurations built from the seed, the reference's semantics on
hand-made cases, and ``BENCHMARK.json`` against its contract."""
import json
import re

import numpy as np
import pytest

from bench import deployment, reference, refloop
from bench.deployment import Flavour, Link, Microservices, Node, Region, \
    Service
from bench.signals import Carbon, Telemetry, carbon_series

from .cells import REPO, SEED

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in SPEC["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_build_is_a_function_of_the_seed(name):
    cfg = deployment.load_config(REPO / CONFIGS[name]["file"])
    a, b = deployment.build(cfg, SEED), deployment.build(cfg, SEED)
    assert a == b
    # published sizes: the seed drives only the traces
    assert deployment.build(cfg, SEED + 1) == a
    assert a.nodes and all(n.cpu > 0 and n.ram_gb > 0 for n in a.nodes)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_traces_are_functions_of_the_seed(name):
    cfg = deployment.load_config(REPO / CONFIGS[name]["file"])
    dep = deployment.build(cfg, SEED)
    s1 = carbon_series(dep.regions, 60, SEED)
    s2 = carbon_series(dep.regions, 90, SEED)
    for r in s1:     # a longer trace shares the shorter one's prefix
        np.testing.assert_array_equal(s1[r], s2[r][:60])
        assert (s1[r] >= 5.0).all()
    regions = [n.region for n in dep.nodes]
    m = Carbon(s1, SEED).scenario_matrix(regions, 30, 6, 4)
    assert m.shape == (4, len(regions))
    np.testing.assert_array_equal(m, Carbon(s1, SEED).scenario_matrix(
        regions, 30, 6, 4))
    assert not np.array_equal(m, Carbon(s1, SEED, 1).scenario_matrix(
        regions, 30, 6, 4))
    tel = Telemetry(dep, 40, SEED)
    E, comm = tel.profiles(30)
    assert len(E) == sum(len(s.flavours) for s in dep.services)
    assert len(comm) == len(dep.links)
    assert all(v > 0 for v in E.values())


def test_boutique_is_the_papers_size():
    cfg = deployment.load_config(REPO / CONFIGS["online-boutique-eu"]["file"])
    dep = deployment.build(cfg, SEED)
    assert len(dep.services) == 10 and len(dep.nodes) == 5
    assert sum(len(s.flavours) for s in dep.services) == 15
    assert len(dep.links) == 14


SVC = {"a": Service("a", (Flavour("big", 2.0, 4.0, 10.0),
                          Flavour("small", 1.0, 2.0, 6.0))),
       "b": Service("b", (Flavour("small", 1.0, 2.0, 3.0),))}
NODES = {"x": Node("x", "x", 2.0, 8.0, 0.1), "y": Node("y", "y", 4.0, 8.0, 0.1)}
DEP = Microservices("two", tuple(SVC.values()), (Link("a", "b", 1.0, 1.0),),
                    tuple(NODES.values()),
                    {"x": Region(100.0, 0.0, 0.0, 0.0),
                     "y": Region(300.0, 0.0, 0.0, 0.0)}, {})
MIX = {"money_weight": 0.0, "pref_weight": 0.0, "emission_weight": 1.0,
       "green_penalty": 5.0}


def _objective(ci_b, P=None, A=None):
    tz = refloop.Tensors(DEP, np.float64)
    E, K = tz.profiles({("a", "big"): 10.0, ("a", "small"): 6.0,
                        ("b", "small"): 3.0}, {("a", "big", "b"): 0.5})
    P = np.zeros((2, 2, 2)) if P is None else P
    A = np.zeros((2, 2)) if A is None else A
    return tz, refloop.Objective(tz, MIX, E, K, P, A, np.array(ci_b, float))


def test_reference_objective_by_hand():
    tz, obj = _objective([[100.0, 300.0]])
    split = tz.arrays({"a": ("big", "x"), "b": ("small", "y")})
    # 10*100 + 3*300 + 0.5 * mean(100, 300)
    assert obj.emissions(*split, [100.0, 300.0]) == 1000 + 900 + 100
    assert obj.value(0, *split) == 1000 + 900 + 100
    together = tz.arrays({"a": ("big", "y"), "b": ("small", "y")})
    assert obj.emissions(*together, [100.0, 300.0]) == 3000 + 900
    # from the split: a to its small flavour (1500), then b beside it
    # on x, where the small flavour has no link (600 + 300)
    f, n = obj.local_search(0, split[0], split[1], split[2], 50)
    best = tz.assignment(split[0], f, n)
    assert best == {"a": ("small", "x"), "b": ("small", "x")}
    assert obj.value(0, *tz.arrays(best)) == 900.0
    # no single move improves it further
    assert obj.deltas(0, *tz.arrays(best)).min() >= 0.0
    placed, f, n = obj.greedy(0)
    assert tz.assignment(placed, f, n) == best
    assert obj.deltas(0, *together).min() < 0.0


def test_reference_penalties_change_the_plan():
    P = np.zeros((2, 2, 2))
    P[0, 1, 0] = 1e3          # AvoidNode(a, small, x), weight x memory
    tz, obj = _objective([[100.0, 300.0]], P=P)
    placed, f, n = obj.greedy(0)
    assert tz.assignment(placed, f, n) == {"a": ("big", "x"),
                                           "b": ("small", "y")}


def test_quantile_is_the_smallest_sample_at_alpha():
    assert refloop.quantile_inf([5.0, 1.0, 4.0, 2.0, 3.0], 0.8) == 4.0
    assert refloop.quantile_inf([1.0], 0.8) == 1.0
    assert refloop.quantile_inf([], 0.8) == float("inf")


def test_reference_feasibility_and_charges():
    ok = {"a": ("big", "x"), "b": ("small", "y")}
    assert reference.violations(SVC, NODES, ok) == 0
    assert reference.violations(SVC, NODES, {"a": ("big", "x")}) == 1
    assert reference.violations(
        SVC, NODES, {"a": ("big", "x"), "b": ("small", "x")}) == 1
    assert reference.violations(
        SVC, NODES, {"a": ("huge", "x"), "b": ("small", "y")}) == 1
    new = {"a": ("small", "x"), "b": ("small", "x")}
    assert reference.switch_charge(ok, new) == (1, 1)
    assert reference.switch_charge({}, new) == (2, 0)
    assert reference.switch_charge(ok, {"a": ("big", "x")}) == (1, 0)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_contract():
    SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert isinstance(SPEC["run_seconds"], int) \
        and 1 <= SPEC["run_seconds"] <= 51
    cells = len(SPEC["workloads"])
    assert 2 + 14 * 24 * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file() and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    used = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
        used.add(w["config"])
    assert used == names
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, cells // 2)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        base = m["name"].split(".")[0]
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file() or \
            (REPO / "bench/metrics" / f"{base}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:     # every cell: setup_s, another e2e,
        reported = [m for m in SPEC["end_to_end"] + SPEC["per_layer"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len([m for m in reported if m in SPEC["end_to_end"]]) >= 2
        assert any(m in SPEC["per_layer"] for m in reported)
    assert len(json.dumps(SPEC)) < 64 * 1024
