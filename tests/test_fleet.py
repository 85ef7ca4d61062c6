"""Fleet planner: multi-tenant plan_many + FleetRuntime + billing.

The load-bearing claims, in test form:

* **uncoupled == sequential, bitwise** — ``plan_many(coupling="none")``
  returns the SAME placements, notes, skipped services, and emissions as
  per-app ``GreenScheduler.plan`` calls, across dense/sparse backends
  and mixed bucket shapes.  Dyadic synth problems make padding and the
  app-axis vmap arithmetically invisible, so this is exact equality,
  not a tolerance.
* **waterfilling never over-commits** — on capacity-scarce fleets the
  per-node fleet load stays within capacity by construction, and the
  highest-priority tenant's plan bit-matches its solo plan (it sees the
  untouched capacity first).
* **one program, cached** — a warm fleet replan touches zero new XLA
  programs (``metrics_scope`` deltas over the planner compile cache).
* **billing decomposes exactly** — each tenant's ledger bill equals the
  plain sum of its runtime-accounted per-tick totals, bitwise.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from test_sparse_lowering import synth_dyadic

from repro.continuum import (
    CarbonTrace,
    REGION_PRESETS,
    RuntimeConfig,
    WorkloadTrace,
)
from repro.core.lowering import ScenarioBatch
from repro.core.problem import PlacementProblem
from repro.core.scheduler import GreenScheduler, SchedulerConfig
from repro.core.types import (
    Application,
    CommunicationLink,
    Flavour,
    FlavourRequirements,
    Infrastructure,
    Node,
    NodeCapabilities,
    Service,
)
from repro.fleet import (
    FleetApp,
    FleetProblem,
    FleetRuntime,
    plan_many,
)
from repro.obs import (
    Observability,
    billing_report,
    render_billing,
    serve_metrics,
)
from repro.obs.registry import MetricsRegistry, metrics_scope

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _fleet_problems(n_apps, backend="dense", infra_seed=0, base_seed=1000):
    """n_apps dyadic problems lowered against ONE shared infrastructure
    (apps vary in service count -> mixed bucket shapes)."""
    _, infra, _, _, _ = synth_dyadic(infra_seed)
    probs, names = [], []
    for i in range(n_apps):
        app, _, comp, comm, cs = synth_dyadic(
            base_seed + i, n_services=5 + (i % 5))
        probs.append(PlacementProblem.build(
            app, infra, comp, comm, cs, backend=backend))
        names.append(f"tenant{i}")
    return probs, tuple(names)


def _sched():
    # dyadic emission weight keeps every objective term exact
    return GreenScheduler(SchedulerConfig(emission_weight=0.25))


def _assert_same_plan(pf, sf, tag=""):
    assert pf.feasible == sf.feasible, tag
    assert pf.notes == sf.notes, tag
    if pf.feasible:
        assert pf.placements == sf.placements, tag
        assert pf.skipped_services == sf.skipped_services, tag
        assert pf.total_emissions_g == sf.total_emissions_g, tag


# ---------------------------------------------------------------------------
# uncoupled parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_uncoupled_matches_sequential(backend):
    sched = _sched()
    probs, names = _fleet_problems(5, backend=backend)
    seq = [sched.plan(p) for p in probs]
    res = plan_many(FleetProblem(apps=tuple(probs), names=names), sched)
    assert len(res) == 5
    for nm, r, s in zip(names, res.results, seq):
        _assert_same_plan(r.plans[0], s.plans[0], nm)
        if r.plans[0].feasible:
            assert float(r.emissions_g[0]) == float(s.emissions_g[0]), nm
    # fleet emissions vector mirrors the per-result values
    finite = np.isfinite(res.emissions_g)
    assert finite.tolist() == res.feasible.tolist()
    # groups/calls bookkeeping: >=1 batched program ran, apps counted
    assert res.stats.calls >= 1
    assert res.stats.apps == 5


def test_single_app_fleet_matches_plan():
    sched = _sched()
    probs, _ = _fleet_problems(1)
    solo = sched.plan(probs[0])
    res = plan_many(FleetProblem(apps=(probs[0],)), sched)
    _assert_same_plan(res.results[0].plans[0], solo.plans[0])
    assert res.fleet.names == ("app0",)


def test_empty_fleet():
    res = plan_many(FleetProblem(apps=()), _sched())
    assert len(res) == 0
    assert res.total_emissions_g == 0.0
    assert res.capacity.violations == 0
    assert res.assignments() == {}


# ---------------------------------------------------------------------------
# coupled capacity
# ---------------------------------------------------------------------------


def test_waterfill_never_overcommits():
    sched = _sched()
    probs, names = _fleet_problems(5)
    prio = tuple(float(5 - i) for i in range(5))
    wf = FleetProblem(apps=tuple(probs), names=names, priority=prio,
                      coupling="waterfill")
    res = plan_many(wf, sched)
    cap = res.capacity
    assert cap.violations == 0
    assert (cap.cpu_load <= cap.cpu_cap + 1e-9).all()
    assert (cap.ram_load <= cap.ram_cap + 1e-9).all()
    # the same fleet planned uncoupled DOES over-commit (the scarcity
    # the waterfill is resolving is real)
    unc = plan_many(FleetProblem(apps=tuple(probs), names=names), sched)
    assert unc.capacity.violations > 0
    # the highest-priority tenant saw untouched capacity: its waterfill
    # plan bit-matches its solo plan
    top = res.fleet.waterfill_order()[0]
    solo = sched.plan(probs[top])
    _assert_same_plan(res.results[top].plans[0], solo.plans[0], "top")


def test_waterfill_priority_reorders_winners():
    sched = _sched()
    probs, names = _fleet_problems(3)
    lo = plan_many(FleetProblem(
        apps=tuple(probs), names=names, priority=(3.0, 2.0, 1.0),
        coupling="waterfill"), sched)
    hi = plan_many(FleetProblem(
        apps=tuple(probs), names=names, priority=(1.0, 2.0, 3.0),
        coupling="waterfill"), sched)
    assert lo.fleet.waterfill_order() == [0, 1, 2]
    assert hi.fleet.waterfill_order() == [2, 1, 0]
    # both orders stay capacity-sound
    assert lo.capacity.violations == 0
    assert hi.capacity.violations == 0


def test_price_coupling_reports_residuals():
    sched = _sched()
    probs, names = _fleet_problems(4)
    res = plan_many(FleetProblem(
        apps=tuple(probs), names=names, coupling="price",
        price_rounds=3), sched)
    assert res.coupling == "price"
    assert 1 <= res.stats.price_rounds <= 3
    # price iteration only discourages over-commit; whatever remains is
    # reported, never hidden
    assert res.capacity.violations >= 0
    for r in res.results:
        assert r.plans[0] is not None


# ---------------------------------------------------------------------------
# compile-cache economics
# ---------------------------------------------------------------------------


def test_warm_fleet_replan_compiles_nothing():
    sched = _sched()
    probs, names = _fleet_problems(4)
    fleet = FleetProblem(apps=tuple(probs), names=names)
    plan_many(fleet, sched)  # warm every bucket-shape group's program
    with metrics_scope() as scope:
        res = plan_many(fleet, sched)
    assert scope.delta("planner.compile.misses") == 0
    assert scope.delta("planner.compile.calls") == res.stats.calls
    assert res.stats.compiles == 0

    wf = FleetProblem(apps=tuple(probs), names=names,
                      coupling="waterfill")
    plan_many(wf, sched)
    with metrics_scope() as scope:
        res2 = plan_many(wf, sched)
    assert scope.delta("planner.compile.misses") == 0
    assert res2.stats.compiles == 0


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_fleet_validation_errors():
    probs, names = _fleet_problems(2)
    with pytest.raises(ValueError, match="unknown coupling"):
        FleetProblem(apps=tuple(probs), coupling="auction")
    with pytest.raises(ValueError, match="unique"):
        FleetProblem(apps=tuple(probs), names=("a", "a"))
    with pytest.raises(ValueError, match="2 names for"):
        FleetProblem(apps=(probs[0],), names=names)
    with pytest.raises(ValueError, match="priorities for"):
        FleetProblem(apps=tuple(probs), priority=(1.0,))
    with pytest.raises(ValueError, match="ScenarioBatch"):
        FleetProblem(apps=(
            probs[0].with_scenarios(ScenarioBatch(
                ci=np.ones((2, probs[0].lowering.N)))),
            probs[1]))
    # different infrastructure -> rejected
    _, other_infra, _, _, _ = synth_dyadic(77)
    app, _, comp, comm, cs = synth_dyadic(1001, n_services=6)
    alien = PlacementProblem.build(app, other_infra, comp, comm, cs)
    with pytest.raises(ValueError, match="share one Infrastructure"):
        FleetProblem(apps=(probs[0], alien))


# ---------------------------------------------------------------------------
# fleet runtime + per-tenant billing
# ---------------------------------------------------------------------------


def _tenant_app(tag, n_services):
    services = tuple(
        Service(f"{tag}-svc{i}", flavours=(
            Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
            Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
        )) for i in range(n_services))
    links = (CommunicationLink(f"{tag}-svc0", f"{tag}-svc1"),)
    return Application(tag, services, links)


def _shared_infra():
    regions = ("solar-south", "wind-north", "coal-east")
    nodes = tuple(
        Node(f"{r}-{k}", region=r, cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=8.0, ram_gb=32.0))
        for r in regions for k in range(2))
    return Infrastructure("shared", nodes)


def test_fleet_runtime_waterfill_and_billing():
    infra = _shared_infra()
    carbon = CarbonTrace(REGION_PRESETS, hours=24, seed=3)
    obs = Observability()
    fas = [
        FleetApp(f"tenant{i}", _tenant_app(f"t{i}", 3 + i),
                 WorkloadTrace(_tenant_app(f"t{i}", 3 + i),
                               seed=i, noise=0.0),
                 priority=float(3 - i))
        for i in range(3)]
    frt = FleetRuntime(fas, infra, carbon,
                       config=RuntimeConfig(horizon_h=4),
                       coupling="waterfill", obs=obs)
    res = frt.run(0, 3)

    assert len(res.ticks) == 3
    assert set(res.results) == {"tenant0", "tenant1", "tenant2"}
    for fr in res.ticks:
        # waterfilled candidates and post-gate active assignments both
        # respect the shared capacity
        assert fr.planned_capacity.violations == 0
        assert fr.capacity.violations == 0
    # warm ticks reuse the tick-0 programs
    assert res.ticks[0].compiles >= 1
    assert res.ticks[1].compiles == 0
    assert res.ticks[2].compiles == 0
    # every tenant got deployed and accounted
    assert res.total_emissions_g > 0
    for fa in fas:
        ticks = res.results[fa.name].ticks
        assert len(ticks) == 3
        assert all(t.replanned for t in ticks)

    # per-tenant bill == that tenant's accounted per-tick totals, bitwise
    rep = billing_report(obs.ledger)
    assert set(rep) == {"tenant0", "tenant1", "tenant2"}
    for fa in fas:
        acct = sum(t.emissions_g + t.migration_g
                   for t in res.results[fa.name].ticks)
        assert rep[fa.name]["total"] == acct, fa.name
        assert rep[fa.name]["ticks"] == 3.0
    # ...and therefore the fleet total decomposes exactly
    assert sum(rep[fa.name]["total"] for fa in fas) == sum(
        sum(t.emissions_g + t.migration_g
            for t in res.results[fa.name].ticks)
        for fa in fas)
    table = render_billing(rep)
    assert "tenant0" in table and "total_g" in table

    summary = res.summary()
    assert summary["apps"] == 3
    assert summary["violations"] == 0


def test_fleet_runtime_rejects_duplicate_names():
    infra = _shared_infra()
    carbon = CarbonTrace(REGION_PRESETS, hours=4, seed=0)
    app = _tenant_app("x", 2)
    wl = WorkloadTrace(app, seed=0)
    with pytest.raises(ValueError, match="unique"):
        FleetRuntime([FleetApp("a", app, wl), FleetApp("a", app, wl)],
                     infra, carbon)


# ---------------------------------------------------------------------------
# metrics endpoint (satellite: serve_metrics)
# ---------------------------------------------------------------------------


def test_serve_metrics_scrapes_live_registry():
    reg = MetricsRegistry()
    reg.inc("fleet.test.counter", 3.0)
    with serve_metrics(reg, port=0) as server:
        url = f"http://127.0.0.1:{server.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert "repro_fleet_test_counter_total 3\n" in body
        reg.inc("fleet.test.counter", 1.0)  # registry is read per scrape
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert "repro_fleet_test_counter_total 4\n" in body
    with pytest.raises(OSError):
        urllib.request.urlopen(url, timeout=1)


def test_serve_metrics_fixed_port_retries_until_free():
    """A fixed-port bind that collides with a live server must retry
    with backoff and succeed once the incumbent releases the port —
    restart-under-supervisor semantics, not a crash."""
    reg = MetricsRegistry()
    reg.inc("fleet.test.counter", 7.0)
    first = serve_metrics(reg, port=0)
    port = first.port

    closer = threading.Timer(0.15, first.close)
    closer.start()
    try:
        # starts while `first` still holds the port: the first attempts
        # hit EADDRINUSE, a later one lands after the timer fires
        second = serve_metrics(reg, port=port, retries=10, backoff_s=0.02)
    finally:
        closer.join()
    try:
        assert second.port == port
        url = f"http://127.0.0.1:{port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert "repro_fleet_test_counter_total 7\n" in body
    finally:
        second.close()


def test_serve_metrics_fixed_port_exhausts_retries():
    reg = MetricsRegistry()
    with serve_metrics(reg, port=0) as first:
        t0 = time.perf_counter()
        with pytest.raises(OSError):
            serve_metrics(reg, port=first.port, retries=2,
                          backoff_s=0.01)
        # it actually backed off (0.01 + 0.02) before giving up
        assert time.perf_counter() - t0 >= 0.03


def test_metrics_server_close_is_idempotent():
    reg = MetricsRegistry()
    server = serve_metrics(reg, port=0)
    server.close()
    server.close()  # second close is a no-op, not an error


# ---------------------------------------------------------------------------
# shard_map over the app axis (subprocess: device count is fixed at
# jax init, so the multi-device path cannot run in this process)
# ---------------------------------------------------------------------------

_SHARDED_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax
from test_sparse_lowering import synth_dyadic
from test_fleet import _fleet_problems, _sched
from repro.fleet import FleetProblem, plan_many

sched = _sched()
probs, names = _fleet_problems(4)
seq = [sched.plan(p) for p in probs]
res = plan_many(FleetProblem(apps=tuple(probs), names=names), sched)
ok = bool(res.stats.sharded) and res.stats.devices == 8
# 5 apps bucket to 8, not a multiple of 3 devices: phantom apps pad the
# app axis to 9 and the program still shards
probs5, names5 = _fleet_problems(5)
seq5 = [sched.plan(p) for p in probs5]
res5 = plan_many(FleetProblem(apps=tuple(probs5), names=names5), sched,
                 devices=jax.devices()[:3])
ok = ok and bool(res5.stats.sharded) and res5.stats.devices == 3
ok = ok and res5.stats.padded_apps == 4
for r, s in zip(res.results + res5.results, seq + seq5):
    pf, sf = r.plans[0], s.plans[0]
    ok = ok and pf.feasible == sf.feasible and pf.notes == sf.notes
    if pf.feasible:
        ok = ok and pf.placements == sf.placements
        ok = ok and pf.total_emissions_g == sf.total_emissions_g
print(json.dumps({{"ok": ok}}))
"""


@pytest.mark.slow
def test_sharded_fleet_matches_sequential_subprocess():
    code = _SHARDED_PARITY.format(
        src=os.path.abspath(SRC),
        tests=os.path.abspath(os.path.dirname(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"]
