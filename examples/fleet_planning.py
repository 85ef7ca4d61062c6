"""Fleet planning: many tenants, one shared green continuum.

Builds a small multi-tenant fleet — several applications, each with its
own workload trace and priority, competing for ONE infrastructure — and
shows the three capacity-coupling modes of ``repro.fleet.plan_many``:

* ``"none"``      — every tenant sees the full capacity (bit-identical
  to per-app ``plan`` calls); over-commit is reported, not prevented;
* ``"waterfill"`` — tenants plan in priority order against the capacity
  the higher-priority tenants left behind (never over-commits);
* ``"price"``     — per-node shadow prices steer the fully parallel
  batched program away from contested nodes.

Then drives the whole fleet through a day of the adaptive continuum
loop (``FleetRuntime``: one batched replan per tick, per-app hysteresis)
with the emissions ledger attached, and prints each tenant's carbon
bill — whose totals decompose the fleet's accounted emissions exactly.

  PYTHONPATH=src python examples/fleet_planning.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.continuum import (
    CarbonTrace,
    REGION_PRESETS,
    RuntimeConfig,
    WorkloadTrace,
)
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
from repro.fleet import FleetApp, FleetProblem, FleetRuntime, plan_many
from repro.obs import Observability, billing_report, render_billing
from repro.jax_cache import enable_persistent_cache


def tenant_app(tag: str, n_services: int) -> Application:
    services = tuple(
        Service(f"{tag}-svc{i}", flavours=(
            Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
            Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
        )) for i in range(n_services))
    links = (CommunicationLink(f"{tag}-svc0", f"{tag}-svc1"),)
    return Application(tag, services, links)


def shared_infra(carbon_by_region=None) -> Infrastructure:
    regions = ("solar-south", "wind-north", "coal-east")
    nodes = tuple(
        Node(f"{r}-{k}", region=r, cost_per_cpu_hour=0.5,
             carbon=(carbon_by_region or {}).get(r),
             capabilities=NodeCapabilities(cpu=8.0, ram_gb=32.0))
        for r in regions for k in range(2))
    return Infrastructure("continuum", nodes)


def main() -> None:
    enable_persistent_cache()
    infra = shared_infra()
    carbon = CarbonTrace(REGION_PRESETS, hours=48, seed=11)
    sched = GreenScheduler(SchedulerConfig(emission_weight=1.0))

    # -- one-shot: the three coupling modes on the same fleet ---------
    # (static per-region carbon for the one-shot; the runtime below
    # gets the live trace through the constraint pipeline instead)
    apps = {f"tenant{i}": tenant_app(f"t{i}", 3 + i) for i in range(4)}
    static = shared_infra({"solar-south": 80.0, "wind-north": 120.0,
                           "coal-east": 520.0})
    probs = tuple(
        PlacementProblem.build(
            app, static,
            {(s.component_id, f.name): 20.0 * f.requirements.cpu
             for s in app.services for f in s.flavours},
            {}, [])
        for app in apps.values())
    names = tuple(apps)
    prio = tuple(float(len(apps) - i) for i in range(len(apps)))
    print("== one-shot plan_many, three coupling modes ==")
    for coupling in ("none", "waterfill", "price"):
        fleet = FleetProblem(apps=probs, names=names, priority=prio,
                             coupling=coupling)
        res = plan_many(fleet, sched)
        feas = int(res.feasible.sum())
        print(f"  {coupling:<10} feasible {feas}/{len(fleet)}, "
              f"violated nodes {res.capacity.violations}, "
              f"total {res.total_emissions_g:10.2f} g, "
              f"{res.stats.calls} program call(s)")

    # -- a day of the fleet's adaptive loop, billed per tenant --------
    print("\n== 24 ticks of FleetRuntime (waterfill) ==")
    obs = Observability()
    fas = [FleetApp(name, tenant_app(f"t{i}", 3 + i),
                    WorkloadTrace(tenant_app(f"t{i}", 3 + i),
                                  seed=i, noise=0.0),
                    priority=float(len(apps) - i))
           for i, name in enumerate(apps)]
    frt = FleetRuntime(fas, infra, carbon, config=RuntimeConfig(),
                       coupling="waterfill", obs=obs)
    res = frt.run(0, 24)
    s = res.summary()
    print(f"  {s['apps']:.0f} tenants, {s['ticks']:.0f} ticks: "
          f"{s['total_emissions_g']:.1f} g total, "
          f"{s['switches']:.0f} switches, "
          f"{s['violations']:.0f} capacity violations")
    print("\n== per-tenant carbon bill ==")
    print(render_billing(billing_report(obs.ledger)), end="")


if __name__ == "__main__":
    main()
