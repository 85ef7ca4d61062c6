"""Fleet planner scale: 1000 tenants, one shared continuum, one program.

Sweeps the app axis (same per-app shape: S~=50 services, N=200 shared
nodes) through ``repro.fleet.plan_many`` and records:

* **throughput** — warm fleet replan wall time, total and per app, for
  the uncoupled and the waterfill-coupled paths;
* **compile economics** — the entire fleet must run as ONE batched
  program per (backend, bucket-shape) group: cold compiles stay at "a
  handful" (<= ``COMPILE_CEILING``, independent of A) and a warm replan
  touches ZERO new XLA programs (``metrics_scope`` over the planner
  compile cache, ``calls`` must equal ``FleetStats.calls``);
* **capacity soundness** — waterfilling reports zero violated nodes by
  construction, while the same fleet planned uncoupled is allowed (and
  at saturation expected) to over-commit — the delta is what the
  coupling buys;
* **per-tenant billing** — a short ``FleetRuntime`` run over a shared
  carbon trace with the emissions ledger attached: each tenant's billed
  total must equal the plain sum of its runtime-accounted per-tick
  emissions, bitwise.

Merges a ``fleet`` section into ``BENCH_scheduler.json`` (full runs
only) so the scale trajectory is tracked PR-over-PR.

  PYTHONPATH=src python -m benchmarks.fleet_scale [--smoke] [--check]
"""
import argparse
import json
import os
import time

from benchmarks.scheduler_scalability import synth

from repro.core.problem import PlacementProblem
from repro.core.scheduler import GreenScheduler, SchedulerConfig
from repro.fleet import FleetProblem, plan_many
from repro.jax_cache import enable_persistent_cache
from repro.obs import metrics_scope

OUT_JSON = "BENCH_scheduler.json"

# Cold XLA programs for the whole sweep, both coupling modes, all fleet
# sizes: one uncoupled + one waterfill program per bucket-shape group
# (all apps share one group here), NOT one per app or per fleet size.
COMPILE_CEILING = 6


def build_fleet(n_apps, n_services=50, n_nodes=200, seed=0):
    """n_apps distinct problems (varied computation/communication/soft
    constraints) lowered against ONE shared infrastructure."""
    _, infra, _, _, _ = synth(n_services, n_nodes, seed=seed)
    probs = []
    for i in range(n_apps):
        app, _, comp, comm, cs = synth(n_services, n_nodes, seed=seed + 1 + i)
        probs.append(PlacementProblem.build(app, infra, comp, comm, cs))
    return tuple(probs)


def _timed(fn, repeats=1):
    best, out = None, None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, out


def sweep(report, apps_axis, n_services, n_nodes, sched, repeats, check):
    rows = []
    with metrics_scope() as cold_scope:
        for n_apps in apps_axis:
            t0 = time.perf_counter()
            probs = build_fleet(n_apps, n_services, n_nodes)
            build_s = time.perf_counter() - t0
            names = tuple(f"tenant{i}" for i in range(n_apps))
            prio = tuple(float(n_apps - i) for i in range(n_apps))

            unc = FleetProblem(apps=probs, names=names)
            wf = FleetProblem(apps=probs, names=names, priority=prio,
                              coupling="waterfill")
            plan_many(unc, sched)   # compile warmup: steady state is
            plan_many(wf, sched)    # what the fleet tick replans
            with metrics_scope() as warm:
                t_unc, r_unc = _timed(lambda: plan_many(unc, sched),
                                      repeats)
                t_wf, r_wf = _timed(lambda: plan_many(wf, sched), repeats)
            warm_misses = int(warm.delta("planner.compile.misses"))
            warm_calls = int(warm.delta("planner.compile.calls"))
            expect_calls = repeats * (r_unc.stats.calls + r_wf.stats.calls)

            row = {
                "apps": n_apps, "services": n_services, "nodes": n_nodes,
                "build_s": build_s,
                "uncoupled": {
                    "plan_s": t_unc, "per_app_ms": 1e3 * t_unc / n_apps,
                    "calls": r_unc.stats.calls,
                    "feasible": int(r_unc.feasible.sum()),
                    "violations": r_unc.capacity.violations,
                },
                "waterfill": {
                    "plan_s": t_wf, "per_app_ms": 1e3 * t_wf / n_apps,
                    "calls": r_wf.stats.calls,
                    "feasible": int(r_wf.feasible.sum()),
                    "violations": r_wf.capacity.violations,
                },
                "warm_compile_misses": warm_misses,
            }
            rows.append(row)
            report(f"  A={n_apps:>5}: build {build_s:6.1f}s | "
                   f"uncoupled {t_unc:7.3f}s "
                   f"({row['uncoupled']['per_app_ms']:6.2f}ms/app, "
                   f"{r_unc.capacity.violations} violated nodes) | "
                   f"waterfill {t_wf:7.3f}s "
                   f"({row['waterfill']['per_app_ms']:6.2f}ms/app, "
                   f"{r_wf.capacity.violations} violated, "
                   f"{int(r_wf.feasible.sum())}/{n_apps} feasible)")

            if check:
                assert r_wf.capacity.violations == 0, \
                    "waterfilling over-committed a node"
                assert warm_misses == 0, (
                    f"warm fleet replan recompiled: {warm_misses} misses")
                assert warm_calls == expect_calls, (warm_calls,
                                                    expect_calls)
    cold_compiles = int(cold_scope.delta("planner.compile.misses"))
    report(f"  cold XLA programs across the whole sweep: {cold_compiles} "
           f"(ceiling {COMPILE_CEILING})")
    if check:
        assert cold_compiles <= COMPILE_CEILING, cold_compiles
    return rows, cold_compiles


def billing_run(report, n_tenants, ticks, check):
    """Short fleet-runtime trace with the ledger attached: per-tenant
    bills must decompose the accounted totals bitwise."""
    from repro.continuum import (
        CarbonTrace, REGION_PRESETS, RuntimeConfig, WorkloadTrace)
    from repro.core.types import (
        Application, CommunicationLink, Flavour, FlavourRequirements,
        Infrastructure, Node, NodeCapabilities, Service)
    from repro.fleet import FleetApp, FleetRuntime
    from repro.obs import Observability, billing_report, render_billing

    def tenant_app(tag, n_services):
        services = tuple(
            Service(f"{tag}-svc{i}", flavours=(
                Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
                Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
            )) for i in range(n_services))
        return Application(tag, services,
                           (CommunicationLink(f"{tag}-svc0",
                                              f"{tag}-svc1"),))

    regions = ("solar-south", "wind-north", "coal-east")
    nodes = tuple(
        Node(f"{r}-{k}", region=r, cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=16.0, ram_gb=64.0))
        for r in regions for k in range(3))
    infra = Infrastructure("shared", nodes)
    carbon = CarbonTrace(REGION_PRESETS, hours=ticks + 25, seed=7)
    obs = Observability()
    fas = [FleetApp(f"tenant{i}", tenant_app(f"t{i}", 3 + i % 3),
                    WorkloadTrace(tenant_app(f"t{i}", 3 + i % 3),
                                  seed=i, noise=0.0),
                    priority=float(n_tenants - i))
           for i in range(n_tenants)]
    frt = FleetRuntime(fas, infra, carbon,
                       config=RuntimeConfig(horizon_h=4),
                       coupling="waterfill", obs=obs)
    res = frt.run(0, ticks)
    rep = billing_report(obs.ledger)
    report(render_billing(rep).rstrip("\n"))
    exact = True
    for fa in fas:
        acct = sum(t.emissions_g + t.migration_g
                   for t in res.results[fa.name].ticks)
        exact = exact and rep[fa.name]["total"] == acct
    violations = sum(fr.violations for fr in res.ticks)
    report(f"  {n_tenants} tenants x {ticks} ticks: billed total "
           f"{sum(r['total'] for r in rep.values()):.3f}g, "
           f"bit-exact decomposition: {exact}, "
           f"active-capacity violations: {violations}")
    if check:
        assert exact, "per-tenant bills drifted from accounted emissions"
        assert violations == 0
    return {
        "tenants": n_tenants, "ticks": ticks,
        "bit_exact": exact, "violations": violations,
        "rows": {k: dict(v) for k, v in rep.items()},
    }


def run(report=print, smoke=False, check=None, out_json=OUT_JSON):
    check = True if check is None else check
    if smoke:
        apps_axis, n_services, n_nodes, repeats = (8, 32), 12, 24, 1
        tenants, ticks = 3, 3
    else:
        apps_axis, n_services, n_nodes, repeats = (100, 300, 1000), 50, 200, 2
        tenants, ticks = 5, 6
    # dyadic emission weight + few local-search rounds: the fleet tick
    # replans every app every tick, so steady-state throughput is the
    # honest number (cold compile is counted separately)
    sched = GreenScheduler(SchedulerConfig(
        emission_weight=0.25, local_search_rounds=2))

    report(f"# Fleet scale: apps axis {apps_axis}, S={n_services}, "
           f"N={n_nodes} shared nodes, best of {repeats}")
    rows, cold_compiles = sweep(report, apps_axis, n_services, n_nodes,
                                sched, repeats, check)

    report(f"# Per-tenant billing ({tenants} tenants, {ticks} ticks, "
           "waterfill fleet runtime)")
    billing = billing_run(report, tenants, ticks, check)

    section = {
        "sweep": rows,
        "cold_compiles": cold_compiles,
        "compile_ceiling": COMPILE_CEILING,
        "billing": billing,
    }
    if out_json:
        blob = {}
        if os.path.exists(out_json):
            with open(out_json) as fh:
                blob = json.load(fh)
        blob["fleet"] = section
        with open(out_json, "w") as fh:
            json.dump(blob, fh, indent=2)
        report(f"# merged 'fleet' into {out_json}")
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fleet for CI; does not overwrite the "
                         "tracked BENCH json")
    ap.add_argument("--check", action="store_true",
                    help="enforce the capacity/compile/billing gates")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args()
    enable_persistent_cache()
    run(smoke=args.smoke, check=args.check or None,
        out_json=None if (args.no_json or args.smoke) else OUT_JSON)


if __name__ == "__main__":
    main()
