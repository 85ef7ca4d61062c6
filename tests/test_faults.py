"""Fault injection, degraded-mode planning, and emergency repair.

Covers the :mod:`repro.faults` package (FaultTrace schedules, the
DegradedCarbon / DegradedWorkload planning views, the post-plan
placement validator) and its wiring through the continuum runtime: node
outages must evict and emergency-replan in the SAME tick (bypassing —
but still billing — the hysteresis gate), value-level faults must keep
eager/scanned bit-parity, capacity derates must trip a structured
``run_scanned`` fallback, and every fault surfaces exactly one
observability event.
"""
import types

import numpy as np
import pytest

from test_megaloop import START, _scenario, _runtime

from repro.continuum import FallbackReason
from repro.continuum.megaloop import _Fallback
from repro.faults import (
    DegradedCarbon,
    DegradedWorkload,
    FaultEvent,
    FaultTrace,
    PlacementInvariantError,
    assert_valid,
    check_placement,
)
from repro.fleet import FleetApp, FleetRuntime
from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    REGION_PRESETS,
    RuntimeConfig,
    WorkloadTrace,
)
from repro.core.problem import PlacementProblem
from repro.obs import Observability

REGIONS = ("solar-south", "wind-north", "coal-east")


def _node_ids(infra):
    return [n.node_id for n in infra.nodes]


def _faults(infra, ticks, events):
    return FaultTrace.from_events(_node_ids(infra), REGIONS,
                                  START + ticks, events)


def _outage_events():
    """The carbon planner parks everything on wind-north (lowest CI), so
    outages must hit wind-north nodes to actually strand services."""
    return [
        FaultEvent("node_outage", "wind-north-0", START + 8, 6),
        FaultEvent("node_outage", "wind-north-1", START + 11, 3),
        FaultEvent("zone_blackout", "wind-north", START + 12, 5),
        FaultEvent("telemetry_dropout", "", START + 20, 2),
        FaultEvent("workload_spike", "", START + 18, 3, 2.0),
    ]


# ---------------------------------------------------------------------------
# FaultTrace: schedules
# ---------------------------------------------------------------------------


def test_fault_trace_generate_is_deterministic_and_never_total():
    ids = [f"n{i}" for i in range(4)]
    a = FaultTrace.generate(ids, REGIONS, 96, seed=3, node_outages=5)
    b = FaultTrace.generate(ids, REGIONS, 96, seed=3, node_outages=5)
    assert np.array_equal(a.alive, b.alive)
    assert np.array_equal(a.zone_dark, b.zone_dark)
    assert np.array_equal(a.telemetry_drop, b.telemetry_drop)
    assert np.array_equal(a.spike, b.spike)
    assert a.events == b.events
    c = FaultTrace.generate(ids, REGIONS, 96, seed=4, node_outages=5)
    assert not np.array_equal(a.alive, c.alive)
    # outages are re-drawn rather than allowed to kill every node at once
    assert a.alive.any(axis=1).all()


def test_fault_trace_accessors_out_of_range_are_fault_free():
    ft = FaultTrace.generate(["n0", "n1"], REGIONS, 10, seed=0,
                             telemetry_dropouts=1, zone_blackouts=1)
    for t in (-1, 10, 99):
        assert ft.alive_at(t).all()
        assert not ft.dropout_at(t)
        assert ft.spike_at(t) == 1.0
        assert ft.derate_at(t) is None
        assert ft.staleness(REGIONS[0], t) == 0


def test_fault_trace_staleness_counts_consecutive_dark_ticks():
    ft = FaultTrace.from_events(
        ["n0"], REGIONS, 12,
        [FaultEvent("zone_blackout", "wind-north", 3, 4)])
    assert [ft.staleness("wind-north", t) for t in range(9)] == \
        [0, 0, 0, 1, 2, 3, 4, 0, 0]
    assert ft.staleness("coal-east", 4) == 0


def test_fault_trace_rejects_bad_targets_and_derates():
    with pytest.raises(ValueError, match="unknown node"):
        FaultTrace.from_events(
            ["n0"], REGIONS, 8,
            [FaultEvent("node_outage", "nope", 1, 2)])
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultTrace.from_events(
            ["n0"], REGIONS, 8, [FaultEvent("meteor", "n0", 1, 2)])
    with pytest.raises(ValueError, match="derate factors"):
        FaultTrace.from_events(
            ["n0"], REGIONS, 8,
            [FaultEvent("capacity_derate", "n0", 1, 2, 0.0)])


def test_fault_trace_check_infra_enforces_node_order():
    _, infra = _scenario(n_services=2)
    ids = _node_ids(infra)
    FaultTrace.none(ids, REGIONS, 4).check_infra(infra)  # matching: fine
    with pytest.raises(ValueError, match="node order"):
        FaultTrace.none(ids[::-1], REGIONS, 4).check_infra(infra)


# ---------------------------------------------------------------------------
# validator
# ---------------------------------------------------------------------------


def _toy_low():
    # S=2 services x F=1 flavour, N=2 nodes; validator only touches the
    # lowering's tensor surface, so a namespace stands in for the real one
    return types.SimpleNamespace(
        S=2, N=2,
        service_ids=("a", "b"), node_ids=("n0", "n1"),
        cpu_req=np.array([[2.0], [2.0]]),
        ram_req=np.array([[1.0], [1.0]]),
        cpu_cap=np.array([3.0, 3.0]),
        ram_cap=np.array([8.0, 8.0]))


def test_validator_flags_dead_node_and_over_capacity():
    low = _toy_low()
    placed = np.array([True, True])
    fcur = np.zeros(2, np.int64)

    # both services on n0: cpu 4 > cap 3
    over = check_placement(low, placed, fcur, np.zeros(2, np.int64), t=5)
    assert [v.kind for v in over] == ["over_capacity"]
    assert over[0].node == "n0" and over[0].t == 5

    # spread out, but n1 is dead
    dead = check_placement(low, placed, fcur,
                           np.array([0, 1], np.int64),
                           alive=np.array([True, False]), t=6)
    assert [v.kind for v in dead] == ["dead_node"]
    assert dead[0].service == "b" and dead[0].node == "n1"

    clean = check_placement(low, placed, fcur,
                            np.array([0, 1], np.int64),
                            alive=np.array([True, True]))
    assert clean == []
    assert_valid(clean)
    with pytest.raises(PlacementInvariantError, match="dead_node"):
        assert_valid(dead)


# ---------------------------------------------------------------------------
# degraded views
# ---------------------------------------------------------------------------


def test_degraded_carbon_freezes_dark_zone_but_accounts_truth():
    carbon = CarbonTrace(REGION_PRESETS, hours=48, seed=1)
    ft = FaultTrace.from_events(
        ["x"], REGIONS, 48,
        [FaultEvent("zone_blackout", "wind-north", 10, 6)])
    view = DegradedCarbon(carbon, ft)
    true_series = carbon.series("wind-north")
    seen = view.series("wind-north")
    # persistence: every dark tick reports the last pre-blackout value
    assert (seen[10:16] == true_series[9]).all()
    assert np.array_equal(seen[:10], true_series[:10])
    assert np.array_equal(seen[16:], true_series[16:])
    # the un-darkened zones pass through untouched
    assert np.array_equal(view.series("coal-east"),
                          carbon.series("coal-east"))
    # accounting/oracle signals stay TRUE even mid-blackout
    regions = ["wind-north", "coal-east"]
    assert np.array_equal(view.now(regions, 12), carbon.now(regions, 12))
    assert np.array_equal(view.future_matrix(regions, 12),
                          carbon.future_matrix(regions, 12))


def test_degraded_carbon_scenarios_match_base_until_stale_then_widen():
    carbon = CarbonTrace(REGION_PRESETS, hours=48, seed=1)
    ft = FaultTrace.from_events(
        ["x"], REGIONS, 48,
        [FaultEvent("zone_blackout", "wind-north", 10, 6)])
    view = DegradedCarbon(carbon, ft, widen_per_stale_h=0.5)
    regions = ["wind-north", "coal-east"]
    # no blackout active: bit-identical ensemble (same seed substream)
    assert np.array_equal(view.scenario_matrix(regions, 5, B=4),
                          carbon.scenario_matrix(regions, 5, B=4))
    # three ticks dark: the hedging ensemble spreads wider than truth's
    stale_v = view.scenario_matrix(regions, 12, B=8)
    stale_b = carbon.scenario_matrix(regions, 12, B=8)
    assert not np.array_equal(stale_v, stale_b)
    assert stale_v.std(axis=0)[0] > 0


def test_degraded_workload_nanifies_dropouts_and_holds_clean_profiles():
    app, _ = _scenario(n_services=3)
    wl = WorkloadTrace(app, seed=2)
    ft = FaultTrace.from_events(
        ["x"], REGIONS, 40,
        [FaultEvent("telemetry_dropout", "", 20, 3),
         FaultEvent("workload_spike", "", 8, 2, 2.0)])
    view = DegradedWorkload(wl, ft)

    # dropout: same identities, NaN values — the structural key of the
    # constraint engine must not move
    mon = view.monitoring(21)
    base = wl.monitoring(21)
    assert [(e.service, e.flavour) for e in mon.energy] == \
        [(e.service, e.flavour) for e in base.energy]
    assert all(np.isnan(e.energy_kwh) for e in mon.energy)
    assert all(np.isnan(s.request_volume) for s in mon.traffic)
    assert view.stale(21) and view.stale(23, window=2)
    assert not view.stale(19) and not view.stale(24)

    # the lowering holds the newest clean tick while stale
    held = view.lowering_monitoring(21)
    clean = view.clean(19)
    assert [e.energy_kwh for e in held.energy] == \
        [e.energy_kwh for e in clean.energy]

    # spikes are real load, scaled multiplicatively, never NaN
    spiked = view.monitoring(8)
    assert [e.energy_kwh for e in spiked.energy] == \
        [e.energy_kwh * 2.0 for e in wl.monitoring(8).energy]


# ---------------------------------------------------------------------------
# eager runtime: eviction, emergency repair, flap damping
# ---------------------------------------------------------------------------


def test_outage_evicts_and_repairs_in_the_same_tick():
    app, infra = _scenario(n_services=6)
    ft = _faults(infra, 24, _outage_events())
    rt = _runtime(app, infra, 24, faults=ft)
    res = rt.run(START, 24)

    evicted = [r for r in res.ticks if r.evicted > 0]
    assert evicted, "outages never stranded a service"
    for r in evicted:
        # emergency repair happens INSIDE the eviction tick: replan,
        # forced switch, costs billed
        assert r.emergency and r.replanned and r.switched
        assert r.migration_g > 0
    # the validator ran every tick and found nothing
    assert rt.placement_violations == []
    assert all(r.violations == 0 for r in res.ticks)
    # every service ends on a live node
    assert len(res.final_assignment) == len(app.services)


def test_flap_damping_never_blocks_evacuation():
    """A hysteresis margin high enough to freeze ALL voluntary switches
    must not keep services on (or off) a dead node: the emergency path
    bypasses the gate; the no-emergency control shows the gate really
    was frozen."""
    app, infra = _scenario(n_services=6)
    events = _outage_events()[:2]

    ft = _faults(infra, 20, events)
    rt = _runtime(app, infra, 20, faults=ft, hysteresis_g=1e9)
    res = rt.run(START, 20)
    assert sum(r.evicted for r in res.ticks) > 0
    for r in res.ticks:
        if r.evicted:
            assert r.emergency and r.switched
    assert len(res.final_assignment) == len(app.services)
    assert rt.placement_violations == []

    ft2 = _faults(infra, 20, events)
    rt2 = _runtime(app, infra, 20, faults=ft2, hysteresis_g=1e9,
                   emergency_replan=False)
    res2 = rt2.run(START, 20)
    stranded = [r for r in res2.ticks if r.evicted > 0]
    assert stranded and not any(r.emergency for r in res2.ticks)
    # the gate stayed frozen: evicted services were never re-adopted …
    assert len(res2.final_assignment) < len(app.services)
    # … but nothing infeasible was ever committed either
    assert rt2.placement_violations == []


def test_emergency_charges_land_in_the_ledger_bit_exactly():
    app, infra = _scenario(n_services=6)
    ft = _faults(infra, 24, _outage_events())
    rt = _runtime(app, infra, 24, faults=ft)
    rt.obs = Observability()
    res = rt.run(START, 24)

    assert any(r.emergency for r in res.ticks)
    entries = rt.obs.ledger.entries
    assert len(entries) == len(res.ticks)
    for e, r in zip(entries, res.ticks):
        assert e.emissions_g == r.emissions_g      # bit-equal
        assert e.migration_g == r.migration_g      # emergency moves billed
    em, mig = rt.obs.ledger.totals()
    assert em == sum(r.emissions_g for r in res.ticks)
    assert mig == sum(r.migration_g for r in res.ticks)
    assert mig > 0


# ---------------------------------------------------------------------------
# scanned parity and the structural-fault fallback
# ---------------------------------------------------------------------------

_EXACT = ("t", "emissions_g", "migration_g", "migrations", "replanned",
          "switched", "restarts", "n_constraints", "warm_start_rejected",
          "evicted", "emergency", "violations")


def _assert_fault_parity(res_e, res_s):
    assert len(res_e.ticks) == len(res_s.ticks)
    for a, b in zip(res_e.ticks, res_s.ticks):
        for f in _EXACT:
            assert getattr(a, f) == getattr(b, f), (a.t, f)
        # XLA vs numpy may differ in the last ulp on non-dyadic
        # degraded-carbon values; every decision derived from the
        # saving is checked exactly above
        assert np.isclose(a.expected_saving_g, b.expected_saving_g,
                          rtol=1e-9, atol=1e-9)
    assert res_e.final_assignment == res_s.final_assignment


@pytest.mark.parametrize("emergency", [True, False])
def test_faulty_trace_scanned_parity(emergency):
    app, infra = _scenario(n_services=6)
    ticks = 24
    mk = lambda: _runtime(  # noqa: E731
        app, infra, ticks,
        faults=_faults(infra, ticks, _outage_events()),
        emergency_replan=emergency)
    rt_e, rt_s = mk(), mk()
    res_e = rt_e.run(START, ticks)
    res_s = rt_s.run_scanned(START, ticks)
    assert rt_s.last_scanned_fallback is None
    assert rt_s.scanned_fallbacks == []
    _assert_fault_parity(res_e, res_s)
    assert rt_e.placement_violations == []
    assert rt_s.placement_violations == []
    if emergency:
        assert any(r.emergency for r in res_s.ticks)


def test_capacity_derate_falls_back_to_eager_with_structured_reason():
    app, infra = _scenario(n_services=6)
    ticks = 16
    ft = _faults(infra, ticks, [
        FaultEvent("capacity_derate", "wind-north-0", START + 5, 4, 0.5)])
    rt = _runtime(app, infra, ticks, faults=ft)
    rt.obs = Observability()
    res = rt.run_scanned(START, ticks)

    assert len(rt.scanned_fallbacks) == 1
    ev = rt.scanned_fallbacks[0]
    assert ev.reason is FallbackReason.FAULT_CAPACITY_DERATE
    assert rt.last_scanned_fallback == FallbackReason.FAULT_CAPACITY_DERATE
    # the eager replay still ran the whole window, fault-aware
    assert len(res.ticks) == ticks
    assert rt.placement_violations == []
    # exactly one structured registry event for the fallback
    falls = [e for e in rt.obs.registry.events
             if e["name"] == "runtime.scanned_fallback"]
    assert len(falls) == 1
    assert rt.obs.registry.value("runtime.scanned_fallbacks") == 1.0


def test_fallback_reasons_are_a_closed_enum():
    with pytest.raises(TypeError, match="FallbackReason"):
        _Fallback("some ad-hoc reason string")
    # members stringify to their stable reason text (external contracts:
    # logs, BENCH json, last_scanned_fallback matchers)
    assert str(FallbackReason.ENGINE_KEY_DRIFT) == \
        "engine structural key drifted mid-trace"
    assert str(FallbackReason.FAULT_CAPACITY_DERATE) == \
        "capacity-derate faults change capacity tensors mid-trace"
    assert FallbackReason.FAULT_CAPACITY_DERATE == \
        "capacity-derate faults change capacity tensors mid-trace"


def test_fault_events_surface_exactly_once_on_both_paths():
    app, infra = _scenario(n_services=6)
    ticks = 24
    events = _outage_events()

    def counts(run_name):
        ft = _faults(infra, ticks, events)
        rt = _runtime(app, infra, ticks, faults=ft)
        rt.obs = Observability()
        getattr(rt, run_name)(START, ticks)
        reg = rt.obs.registry
        named = {}
        for e in reg.events:
            named[e["name"]] = named.get(e["name"], 0) + 1
        return named, reg

    eager, reg_e = counts("run")
    scanned, reg_s = counts("run_scanned")
    # one structured event per fault occurrence, at its start tick
    assert eager["fault.node_outage"] == 2
    assert eager["fault.zone_blackout"] == 1
    assert eager["fault.telemetry_dropout"] == 1
    assert eager["fault.workload_spike"] == 1
    assert eager["fault.emergency_replan"] == \
        reg_e.value("runtime.emergency_replans")
    assert "fault.invariant_violation" not in eager
    # the scanned commit replays the same stream, not a duplicate one
    for name in ("fault.node_outage", "fault.zone_blackout",
                 "fault.telemetry_dropout", "fault.workload_spike",
                 "fault.emergency_replan"):
        assert scanned.get(name, 0) == eager.get(name, 0), name
    assert reg_s.value("runtime.evictions") == \
        reg_e.value("runtime.evictions") > 0


# ---------------------------------------------------------------------------
# fleet: shared-capacity faults
# ---------------------------------------------------------------------------


def _tenant(tag, n):
    from repro.core.types import (
        Application, CommunicationLink, Flavour, FlavourRequirements,
        Service)
    services = tuple(
        Service(f"{tag}-svc{i}", flavours=(
            Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
            Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
        )) for i in range(n))
    links = (CommunicationLink(f"{tag}-svc0", f"{tag}-svc1"),)
    return Application(tag, services, links)


def test_fleet_outage_evicts_atomically_and_stays_feasible():
    _, infra = _scenario(n_services=2)
    ticks = 10
    ft = FaultTrace.from_events(
        _node_ids(infra), REGIONS, ticks,
        [FaultEvent("node_outage", "wind-north-0", 4, 3),
         FaultEvent("node_outage", "wind-north-1", 5, 2)])
    carbon = CarbonTrace(REGION_PRESETS, hours=ticks + 25, seed=3)
    apps = [_tenant("ta", 3), _tenant("tb", 3)]
    fas = [FleetApp(a.name, a, WorkloadTrace(a, seed=i, noise=0.0))
           for i, a in enumerate(apps)]
    frt = FleetRuntime(fas, infra, carbon,
                       config=RuntimeConfig(horizon_h=4, faults=ft,
                                            hysteresis_g=1e9),
                       obs=Observability())
    res = frt.run(0, ticks)

    per_app = [res.results[a.name].ticks for a in apps]
    evicted_ticks = [
        t for t in range(ticks)
        if any(recs[t].evicted > 0 for recs in per_app)]
    assert evicted_ticks, "fleet outage never stranded a service"
    for t in evicted_ticks:
        # candidates are only JOINTLY capacity-feasible: an emergency in
        # ANY tenant forces the coupled plan onto EVERY tenant —
        # adopting half of it could overcommit the shared nodes
        assert all(recs[t].emergency for recs in per_app)
    # post-plan invariants (per-app liveness + fleet-level capacity on
    # the summed multi-tenant load) held every tick
    assert frt.placement_violations == []
    assert all(r.violations == 0 for recs in per_app for r in recs)


# the fault tick of the fleet/eager fault-stage comparison, after a
# fault-free warm-up; the green planner keeps off coal-east, so faults on
# every other node reach the incumbent
_FAULT_TICK = 4
_NOT_COAL = ("solar-south-0", "solar-south-1", "wind-north-0",
             "wind-north-1")


@pytest.mark.parametrize("case", ["outage", "derate", "dropout"])
def test_fleet_fault_stage_matches_the_eager_tick(case, monkeypatch):
    """A tenant's fault stage in a FleetRuntime tick is the eager tick's:
    on the same trace, tick and incumbent, the lowering each hands its
    planner (dead nodes masked, capacities derated, dropout profiles
    held), the services evicted and the emergency verdict are equal."""
    import repro.fleet.runtime as fleet_runtime

    _, infra = _scenario(n_services=2)
    t = _FAULT_TICK
    events = {
        "outage": [FaultEvent("node_outage", n, t, 2) for n in _NOT_COAL],
        "derate": [FaultEvent("capacity_derate", n, t, 2, 0.1)
                   for n in _NOT_COAL],
        "dropout": [FaultEvent("telemetry_dropout", "", t, 2)],
    }[case]
    ft = FaultTrace.from_events(_node_ids(infra), REGIONS, t + 2, events)
    carbon = CarbonTrace(REGION_PRESETS, hours=t + 30, seed=3)
    app = _tenant("ta", 3)
    workload = WorkloadTrace(app, seed=0, noise=0.0)
    cfg = RuntimeConfig(horizon_h=4, faults=ft)
    fleet = FleetRuntime([FleetApp(app.name, app, workload)], infra, carbon,
                         config=cfg)
    eager = ContinuumRuntime(app, infra, carbon, workload, config=cfg)
    for k in range(t):
        fleet.tick(k)
        eager.tick(k)
    tenant = fleet.runtime(app.name)
    assert tenant.current
    eager.current = dict(tenant.current)

    seen = {}
    plan_many = fleet_runtime.plan_many

    def fleet_plan(problem, *args, **kwargs):
        seen["fleet"] = problem.apps[0].lowering
        return plan_many(problem, *args, **kwargs)

    evaluate = eager.planner.evaluate

    def eager_plan(problem):
        seen["eager"] = problem.lowering
        return evaluate(problem)

    monkeypatch.setattr(fleet_runtime, "plan_many", fleet_plan)
    monkeypatch.setattr(eager.planner, "evaluate", eager_plan)
    rec_f = fleet.tick(t).records[app.name]
    rec_e = eager.tick(t)

    def key(low):
        return PlacementProblem(lowering=low, constraints=()).fingerprint

    assert key(seen["fleet"]) == key(seen["eager"])
    assert (rec_f.evicted, rec_f.emergency) == \
        (rec_e.evicted, rec_e.emergency)
    low = seen["eager"]
    dead = np.isin(low.node_ids, _NOT_COAL)
    if case == "outage":
        assert rec_e.evicted > 0 and rec_e.emergency
        assert (np.asarray(low.avail_cap)[dead] < 0).all()
    elif case == "derate":
        assert rec_e.evicted == 0 and rec_e.emergency
        assert (np.asarray(low.cpu_cap)[dead] == 0.5).all()
    else:
        assert rec_e.evicted == 0 and not rec_e.emergency
        # the lowering prices the last clean window, not the NaN samples
        assert np.isfinite(low.E).all()
