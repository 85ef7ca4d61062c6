"""Fault recovery on the continuum: 7-day faulty trace, four policies.

A seeded :class:`repro.faults.FaultTrace` (node outages that strand the
green placements, a carbon-zone blackout, a telemetry dropout, a
workload spike) is replayed against four policies on IDENTICAL carbon /
workload traces:

  * ``faulty_adaptive``     — full runtime with emergency replanning:
    stranded services are evicted and re-placed the same tick, bypassing
    the hysteresis gate (migration costs still billed);
  * ``faulty_no_emergency`` — same faults, emergency replanning off:
    evictions still happen, but re-adoption waits for the ordinary
    hysteresis gate — the downtime baseline;
  * ``fault_free``          — same adaptive config, no faults (what the
    outages cost in emissions and migrations);
  * ``faulty_oracle``       — fault-aware oracle: sees the faults, prices
    the TRUE future window, no hysteresis (upper bound under faults).

Gates (``--check``; full runs always check):

  * the trace actually exercises the fault model (>= 3 node outages,
    >= 1 zone blackout, >= 1 telemetry dropout);
  * ZERO post-plan invariant violations (dead-node / over-capacity
    placements) on every policy — the validator runs inside each tick;
  * recovery-to-feasible <= 1 tick with emergency replanning: every
    eviction tick re-places the stranded services in that same tick;
  * eager vs ``run_scanned`` bit-parity on the faulty trace (outages,
    blackout, dropout, spike are all value-level faults): every decision
    and accounting field identical, ``expected_saving_g`` to 1e-9, no
    fallback;
  * capacity derates are STRUCTURAL: ``run_scanned`` on a derated trace
    must fall back loudly with exactly one
    ``FallbackReason.FAULT_CAPACITY_DERATE`` event and replay eagerly
    with zero violations.

Merges a ``fault_recovery`` section into ``BENCH_continuum.json``.

  PYTHONPATH=src python -m benchmarks.fault_recovery [--smoke] [--check]
"""
import argparse
import json
import os
import time

import numpy as np

from benchmarks.continuum_loop import OUT_JSON, _carbon_planner, build_scenario

from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    FallbackReason,
    REGION_PRESETS,
    RuntimeConfig,
    WorkloadTrace,
)
from repro.core.pipeline import GreenConstraintPipeline
from repro.faults import FaultEvent, FaultTrace
from repro.jax_cache import enable_persistent_cache

REGIONS = ("solar-south", "wind-north", "coal-east")
# Decision/accounting fields that must be IDENTICAL between the eager
# and scanned paths on a value-level faulty trace.  expected_saving_g is
# compared to 1e-9 instead: XLA and numpy may disagree in the last ulp
# on non-dyadic degraded-carbon values (every decision derived from it
# is still exact).
EXACT_FIELDS = ("t", "emissions_g", "migration_g", "migrations",
                "replanned", "switched", "restarts", "n_constraints",
                "warm_start_rejected", "evicted", "emergency",
                "violations")
MAX_RECOVERY_TICKS = 1


def fault_events(start, ticks):
    """Deterministic schedule aimed at the green placements: the carbon
    planner parks services on wind-north (lowest CI), so the outages
    must hit wind-north nodes to actually strand services.  The two
    wind-north outages overlap, forcing a full evacuation of the clean
    region for a few ticks."""
    t0 = start
    ev = [
        FaultEvent("node_outage", "wind-north-0", t0 + 11, 8),
        FaultEvent("node_outage", "wind-north-1", t0 + 14, 4),
        FaultEvent("node_outage", "solar-south-0", t0 + 26, 3),
        FaultEvent("zone_blackout", "wind-north", t0 + 16, 6),
        FaultEvent("telemetry_dropout", "", t0 + 34, 3),
        FaultEvent("workload_spike", "", t0 + 30, 4, 2.0),
    ]
    if ticks >= 96:  # the full week gets a second round of weather
        ev += [
            FaultEvent("node_outage", "wind-north-1", t0 + 96, 6),
            FaultEvent("node_outage", "coal-east-0", t0 + 120, 5),
            FaultEvent("zone_blackout", "solar-south", t0 + 110, 12),
            FaultEvent("telemetry_dropout", "", t0 + 140, 4),
        ]
    return [e for e in ev if e.start + e.hours <= start + ticks]


def make_runtime(app, infra, carbon, workload, config):
    return ContinuumRuntime(
        app, infra, carbon, workload, config=config,
        pipeline=GreenConstraintPipeline(), planner=_carbon_planner())


def recovery_ticks(records):
    """Per eviction tick: 1 if the stranded services were re-placed by a
    plan switch in that same tick, else 1 + ticks until the next switch
    (censored at end of trace).  "Feasible again within the tick the
    fault landed" reads as 1."""
    out = []
    for i, r in enumerate(records):
        if r.evicted <= 0:
            continue
        lag = next((j for j, rr in enumerate(records[i:]) if rr.switched),
                   len(records) - i)
        out.append(1 + lag if lag else 1)
    return out


def run_policies(report, app, infra, carbon, workload, faults, start,
                 ticks, B):
    configs = {
        "faulty_adaptive": RuntimeConfig(
            scenarios=B, hysteresis_g=30.0, faults=faults),
        "faulty_no_emergency": RuntimeConfig(
            scenarios=B, hysteresis_g=30.0, faults=faults,
            emergency_replan=False),
        "fault_free": RuntimeConfig(scenarios=B, hysteresis_g=30.0),
        "faulty_oracle": RuntimeConfig(
            oracle=True, hysteresis_g=0.0, horizon_h=1, faults=faults),
    }
    report(f"{'policy':>20} {'emissions_g':>12} {'migr_g':>8} "
           f"{'migs':>5} {'evict':>6} {'emerg':>6} {'viol':>5} "
           f"{'recovery':>9}")
    rows = {}
    for name, cfg in configs.items():
        rt = make_runtime(app, infra, carbon, workload, cfg)
        t0 = time.perf_counter()
        res = rt.run(start=start, ticks=ticks)
        wall = time.perf_counter() - t0
        recs = res.ticks
        rec = recovery_ticks(recs)
        rows[name] = {
            **res.summary(),
            "evicted": sum(r.evicted for r in recs),
            "emergencies": sum(r.emergency for r in recs),
            "violations": len(rt.placement_violations),
            "recovery_ticks": rec,
            "max_recovery_ticks": max(rec) if rec else 0,
            "wall_s": wall,
        }
        r = rows[name]
        report(f"{name:>20} {r['total_emissions_g']:>12.1f} "
               f"{r['migration_emissions_g']:>8.1f} {r['migrations']:>5} "
               f"{r['evicted']:>6} {r['emergencies']:>6} "
               f"{r['violations']:>5} {r['max_recovery_ticks']:>9}")
    return rows


def parity_run(report, app, infra, carbon, workload, faults, start,
               ticks, B):
    """Eager vs scanned on the SAME faulty trace: every fault here is
    value-level (no derates), so run_scanned must stay on the fused path
    and bit-match the eager loop."""
    mk = lambda: make_runtime(  # noqa: E731
        app, infra, carbon, workload,
        RuntimeConfig(scenarios=B, hysteresis_g=30.0, faults=faults))
    rt_e, rt_s = mk(), mk()
    res_e = rt_e.run(start=start, ticks=ticks)
    res_s = rt_s.run_scanned(start=start, ticks=ticks)
    mismatches = []
    for re_, rs_ in zip(res_e.ticks, res_s.ticks):
        for f in EXACT_FIELDS:
            if getattr(re_, f) != getattr(rs_, f):
                mismatches.append((re_.t, f))
    savings_e = np.array([r.expected_saving_g for r in res_e.ticks])
    savings_s = np.array([r.expected_saving_g for r in res_s.ticks])
    saving_close = bool(np.allclose(savings_e, savings_s, rtol=1e-9,
                                    atol=1e-9))
    out = {
        "ticks": ticks,
        "mismatched_fields": len(mismatches),
        "saving_close_1e9": saving_close,
        "fallbacks": len(rt_s.scanned_fallbacks),
        "final_assignment_equal":
            res_e.final_assignment == res_s.final_assignment,
        "violations_eager": len(rt_e.placement_violations),
        "violations_scanned": len(rt_s.placement_violations),
    }
    report(f"  eager vs scanned on the faulty trace: "
           f"{out['mismatched_fields']} field mismatches, "
           f"saving<=1e-9: {saving_close}, "
           f"fallbacks: {out['fallbacks']}, violations: "
           f"{out['violations_eager']}/{out['violations_scanned']}")
    return out


def derate_fallback_run(report, app, infra, carbon, workload, start,
                        ticks, B):
    """Capacity derates change the capacity tensors mid-trace, which the
    fused scan treats as constants: run_scanned must refuse the fused
    path with ONE structured FAULT_CAPACITY_DERATE event and replay the
    whole window eagerly — still fault-aware, still validated."""
    node_ids = [n.node_id for n in infra.nodes]
    ft = FaultTrace.from_events(
        node_ids, REGIONS, start + ticks,
        [FaultEvent("capacity_derate", "wind-north-0",
                    start + ticks // 3, 6, 0.5)])
    rt = make_runtime(app, infra, carbon, workload,
                      RuntimeConfig(scenarios=B, hysteresis_g=30.0,
                                    faults=ft))
    res = rt.run_scanned(start=start, ticks=ticks)
    evs = rt.scanned_fallbacks
    out = {
        "ticks": len(res.ticks),
        "fallback_events": len(evs),
        "reason": str(evs[0].reason) if evs else None,
        "reason_is_derate":
            bool(evs) and evs[0].reason is FallbackReason.FAULT_CAPACITY_DERATE,
        "violations": len(rt.placement_violations),
    }
    report(f"  derated trace: {out['fallback_events']} fallback "
           f"(reason: {out['reason']}), eager replay {out['ticks']} "
           f"ticks, {out['violations']} violations")
    return out


def run(report=print, smoke=False, check=None, out_json=OUT_JSON):
    check = True if check is None else check
    start = 24
    ticks = 48 if smoke else 168
    B = 4 if smoke else 8
    n_services = 8

    app, infra = build_scenario(n_services=n_services, regions=REGIONS)
    node_ids = [n.node_id for n in infra.nodes]
    carbon = CarbonTrace(REGION_PRESETS, hours=start + ticks + 25, seed=7)
    workload = WorkloadTrace(app, seed=11)
    events = fault_events(start, ticks)
    faults = FaultTrace.from_events(node_ids, REGIONS, start + ticks,
                                    events)
    kinds = {k: sum(e.kind == k for e in faults.events)
             for k in ("node_outage", "zone_blackout",
                       "telemetry_dropout", "workload_spike")}
    report(f"# Fault recovery: {ticks} ticks, {n_services} services, "
           f"{len(node_ids)} nodes, faults: {kinds}")

    rows = run_policies(report, app, infra, carbon, workload, faults,
                        start, ticks, B)
    report("# Eager/scanned parity and the structural-fault fallback")
    parity = parity_run(report, app, infra, carbon, workload, faults,
                        start, ticks, B)
    derate = derate_fallback_run(report, app, infra, carbon, workload,
                                 start, min(ticks, 40), B)

    adaptive = rows["faulty_adaptive"]
    if check:
        assert kinds["node_outage"] >= 3 and kinds["zone_blackout"] >= 1 \
            and kinds["telemetry_dropout"] >= 1, \
            f"fault trace too tame: {kinds}"
        for name, r in rows.items():
            assert r["violations"] == 0, \
                f"{name}: {r['violations']} placement violations"
        assert adaptive["evicted"] > 0, "outages never stranded a service"
        assert adaptive["emergencies"] > 0
        assert adaptive["max_recovery_ticks"] <= MAX_RECOVERY_TICKS, \
            (f"emergency recovery took "
             f"{adaptive['max_recovery_ticks']} ticks")
        assert parity["mismatched_fields"] == 0
        assert parity["saving_close_1e9"]
        assert parity["fallbacks"] == 0
        assert parity["final_assignment_equal"]
        assert parity["violations_eager"] == 0 \
            and parity["violations_scanned"] == 0
        assert derate["fallback_events"] == 1, \
            f"expected exactly one fallback, got {derate}"
        assert derate["reason_is_derate"], derate["reason"]
        assert derate["violations"] == 0

    section = {
        "scenario": {"ticks": ticks, "services": n_services,
                     "nodes": len(node_ids), "scenarios_B": B,
                     "start": start},
        "fault_events": [
            {"kind": e.kind, "target": e.target, "start": e.start,
             "hours": e.hours, "magnitude": e.magnitude}
            for e in faults.events],
        "policies": rows,
        "faulty_vs_fault_free_overhead_g": (
            adaptive["total_emissions_g"]
            - rows["fault_free"]["total_emissions_g"]),
        "oracle_gap_g": (
            adaptive["total_emissions_g"]
            - rows["faulty_oracle"]["total_emissions_g"]),
        "parity": parity,
        "derate_fallback": derate,
    }
    if out_json:
        blob = {}
        if os.path.exists(out_json):
            with open(out_json) as fh:
                blob = json.load(fh)
        blob["fault_recovery"] = section
        with open(out_json, "w") as fh:
            json.dump(blob, fh, indent=2)
        report(f"# merged 'fault_recovery' into {out_json}")
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trace for CI; does not overwrite the "
                         "tracked BENCH json")
    ap.add_argument("--check", action="store_true",
                    help="enforce the recovery/parity/validator gates "
                         "(full runs always check)")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args()
    enable_persistent_cache()
    run(smoke=args.smoke, check=args.check or None,
        out_json=None if (args.no_json or args.smoke) else OUT_JSON)


if __name__ == "__main__":
    main()
