"""The fleet cell on the CPU at a tiny size of its own: 16 tenants on two
machines a country, each 12 vCPU, so the price rounds leave machines
over-committed and the commit holds switches.  The cell is correct, each
planted fault comes out not correct, switches from dirty incumbents are
compared too, the float32 control does not pass, and the reference's
tenant-batched parts match the one-tenant reference they build on."""
import json

import numpy as np
import pytest

from bench import deployment, fleetref, refloop
from bench.drivers.fleet import tenant_seed
from bench.signals import Carbon, Telemetry, carbon_series

from . import fleet_faults
from .cells import REPO, SEED, bench_copy, run_harness, run_python

CELL = "fleet1k.price-4chip"
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CFG = json.loads((REPO / "bench/configs/boutique-fleet-eu.json").read_text())
KEEP = [n for n in CFG["nodes"] if int(n.split("-")[1]) < 2]
TINY = {"config": {"nodes": {k: CFG["nodes"][k] for k in KEEP},
                   "regions": {k: CFG["regions"][k] for k in KEEP},
                   "node_cpu": 12.0, "node_ram_gb": 24.0},
        "traffic": {"tenants": 16, "max_ticks": 6, "max_batch": 8,
                    "trace_items": 2}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_copy(tmp_path_factory.mktemp("bench"))


def _metrics(kind):
    return {m["name"] for m in SPEC[kind]
            if CELL in m.get("workloads", [CELL])}


def test_fleet_cell_is_correct(root):
    _, line = run_harness(root, CELL, overrides=TINY)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 16 * 6 and line["failed"] == 0
    assert set(line["metrics"]) == _metrics("end_to_end") \
        == {"tick_ms_p50", "setup_s"}


def test_traced_fleet_run_reads_every_layer(root):
    _, line = run_harness(root, CELL, trace=True, overrides=TINY)
    assert line["correct"] is True, line["compared"]
    want = _metrics("per_layer")
    # no device plane in a CPU trace, so no idle share; the rest is read
    assert set(line["metrics"]) == want - {"device_idle.fleet"}
    assert line["metrics"]["window_compiles.fleet"]["value"] == 0.0
    assert all(v["value"] > 0 for k, v in line["metrics"].items()
               if k != "window_compiles.fleet")


def test_planted_faults_are_not_correct(root):
    names = ["none"] + sorted(fleet_faults.FLEET_FAULTS)
    out = fleet_faults.readings(root, CELL, names, SEED, TINY)

    def bad(name):
        return {k for k, (v, lim) in out[name].items() if v > lim}

    assert bad("none") == set()
    assert "plan_errors" in bad("fleet.plans_swapped")
    assert "infeasible" in bad("fleet.overcommitted")
    assert "plan_errors" in bad("fleet.wrong_prices")
    assert "decision_errors" in bad("fleet.held_as_switched")


SWITCHES = """
import json, sys
from bench import harness
from bench.tests import faults, fleet_faults
faults.FAULTS.update(fleet_faults.FLEET_FAULTS)
spec = harness.load_spec()
for w in spec["workloads"]:
    w["chips"] = 1
case = json.loads(sys.argv[1])
out = {}
for name in case["faults"]:
    with (faults.plant(name) if name != "none"
          else faults.contextlib.nullcontext()):
        cell = harness.Cell(case["workload"], case["seed"], False,
                            require_tpu=False, overrides=case["overrides"],
                            spec=spec)
        d = cell.driver
        # every incumbent moved from France to Italy and back, machine for
        # machine: the loads only change places, so they still fit
        for n in d.names:
            rt = d.frt.runtime(n)
            rt.current = {s: (f, case["swap"].get(nid, nid))
                          for s, (f, nid) in rt.current.items()}
        d.incumbent0 = d._committed()
        cell.window(1.0)
        answers = d.answers()
        numbers = d.judge(answers)
    out[name] = {"switched": sum(sum(a.switched) for a in answers),
                 "held": sum(len(a.held) for a in answers),
                 "numbers": {k: [v, lim] for k, (v, lim) in numbers.items()}}
print(json.dumps(out))
"""


def test_switches_are_compared(root):
    """From incumbents on the dirtiest machines the gates switch, the
    capacity adopts some switches and holds others, and the comparison
    checks each: sound it is correct, a switch left uncharged is not."""
    swap = {f"{a}-{k:02d}": f"{b}-{k:02d}" for k in range(2)
            for a, b in (("france", "italy"), ("italy", "france"))}
    case = {"workload": CELL, "faults": ["none", "fleet.switch_uncharged"],
            "seed": SEED, "overrides": TINY, "swap": swap}
    proc = run_python(root, ["-c", SWITCHES, json.dumps(case)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound = out["none"]
    assert sound["switched"] > 0 and sound["held"] > 0
    assert all(v <= lim for v, lim in sound["numbers"].values()), sound
    bad = {k for k, (v, lim) in out["fleet.switch_uncharged"][
        "numbers"].items() if v > lim}
    assert "decision_errors" in bad


CONTROL = """
import json, sys
from bench import control, harness
spec = harness.load_spec()
for w in spec["workloads"]:
    w["chips"] = 1
case = json.loads(sys.argv[1])
for seed in case["seeds"]:
    print(json.dumps(control.readings(
        case["workload"], seed, 1.0, require_tpu=False,
        overrides=case["overrides"], spec=spec)))
"""


def test_float32_control_fails_where_the_program_passes(root):
    case = {"workload": CELL, "seeds": [SEED, SEED + 1], "overrides": TINY}
    proc = run_python(root, ["-c", CONTROL, json.dumps(case)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert len(rows) == 2
    for r in rows:
        lim = r["limits"]
        assert all(r["program"][k] <= lim[k] for k in lim), r
        assert any(r["control"][k] > lim[k] for k in lim), r


# -- the reference's tenant-batched parts against the one-tenant ones ------

MIX = json.loads((REPO / "bench/traffic/fleet-price.json").read_text())


def _fleet(tenants=5, start=24, hours=60):
    cfg = deployment.load_config(REPO / "bench/configs/boutique-fleet-eu.json",
                                 TINY["config"])
    dep = deployment.build(cfg, SEED)
    series = carbon_series(dep.regions, hours, SEED)
    tels = [Telemetry(dep, hours, tenant_seed(SEED, i))
            for i in range(tenants)]
    return dep, series, tels


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_batched_constraint_pass_matches_the_one_tenant_pass(dtype):
    dep, series, tels = _fleet()
    tz = refloop.Tensors(dep, dtype)
    one = [refloop.ConstraintPass(tz, series, MIX) for _ in tels]
    batch = fleetref.FleetConstraintPass(
        tz, series, MIX, len(tels), [(e[0], e[2]) for e in tels[0].edges])
    first = [(s, tz.first[s]) for s in tz.sids]
    for t in range(24, 36):
        prof = [tel.profiles(t) for tel in tels]
        P, A = batch.step(t, np.array([[E[k] for k in first]
                                       for E, _ in prof]),
                          np.array([list(c.values()) for _, c in prof]))
        for i, (E, c) in enumerate(prof):
            P1, A1 = one[i].step(t, E, c)
            np.testing.assert_array_equal(P[i], P1)
            np.testing.assert_array_equal(A[i], A1)


def test_batched_local_search_matches_the_one_tenant_search():
    dep, series, tels = _fleet(tenants=6)
    tz = refloop.Tensors(dep, np.float64)
    ref = fleetref.FleetReference(dep, MIX, series, Carbon(series, SEED),
                                  tels, 24)
    t = 30
    P, A = ref._penalties(t)
    ci = ref.node_ci(t)
    prof = ref.profiles(t)
    rng = np.random.default_rng(SEED)
    lam_c, lam_r = rng.uniform(0, 50, tz.N), rng.uniform(0, 5, tz.N)
    rounds = int(MIX["local_search_rounds"]) * tz.S
    # from scattered placements, each fitting its machines alone
    placed = np.ones((len(tels), tz.S), bool)
    f0 = np.zeros((len(tels), tz.S), np.int64)
    n0 = rng.integers(0, tz.N, size=(len(tels), tz.S))
    starts = list(zip(placed, f0, n0))
    batch = fleetref.FleetObjective(tz, MIX, np.stack([p[0] for p in prof]),
                                    np.stack([p[1] for p in prof]), P, A, ci,
                                    lam_c, lam_r)
    f, n = batch.local_search(placed, f0, n0, rounds)
    moved = 0
    for i, (pl, fi, ni) in enumerate(starts):
        one = fleetref.PricedObjective(tz, MIX, *prof[i], P[i], A[i],
                                       ci[None], lam_c, lam_r)
        f1, n1 = one.local_search(0, pl, fi, ni, rounds)
        np.testing.assert_array_equal(f[i], f1)
        np.testing.assert_array_equal(n[i], n1)
        moved += int((n1 != ni).sum())
    assert moved > 0
