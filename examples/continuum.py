"""Continuum adaptive loop: a microservice app following the sun.

Runs the ContinuumRuntime for three simulated days over synthetic regional
carbon traces (solar/wind/hydro/coal archetypes): each hour the pipeline
re-estimates energy profiles, refreshes the KB-ranked constraints, prices
a forecast ensemble in one batched jit/vmap call, and relocates services
only when the expected saving beats the migration cost — then prints the
per-day emissions of the adaptive loop next to a plan-once baseline.

  PYTHONPATH=src python examples/continuum.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    REGION_PRESETS,
    RuntimeConfig,
    WhatIfPlanner,
    WorkloadTrace,
)
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
from repro.jax_cache import enable_persistent_cache

START, DAYS = 24, 3


def build_app():
    services = tuple(
        Service(f"svc{i}", flavours=(
            Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
            Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
        )) for i in range(8))
    links = (CommunicationLink("svc0", "svc1"),
             CommunicationLink("svc2", "svc3"))
    return Application("continuum-demo", services, links)


def build_infra():
    nodes = tuple(
        Node(f"{region}-{k}", region=region, cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=4.0, ram_gb=16.0))
        for region in ("solar-south", "wind-north", "coal-east")
        for k in range(2))
    return Infrastructure("continuum-demo", nodes)


def run_policy(app, infra, carbon, workload, config):
    runtime = ContinuumRuntime(
        app, infra, carbon, workload, config=config,
        planner=WhatIfPlanner(
            GreenScheduler(SchedulerConfig(emission_weight=1.0))))
    return runtime.run(start=START, ticks=DAYS * 24)


def main():
    enable_persistent_cache()
    app, infra = build_app(), build_infra()
    carbon = CarbonTrace(REGION_PRESETS, hours=START + DAYS * 24 + 25,
                         seed=42)
    workload = WorkloadTrace(app, seed=42)

    adaptive = run_policy(app, infra, carbon, workload,
                          RuntimeConfig(scenarios=8, hysteresis_g=30.0))
    static = run_policy(app, infra, carbon, workload,
                        RuntimeConfig(replan_every=10 ** 9))

    print(f"{'day':>4} {'adaptive_g':>11} {'static_g':>9}")
    for d in range(DAYS):
        a = sum(r.emissions_g + r.migration_g
                for r in adaptive.ticks[d * 24:(d + 1) * 24])
        s = sum(r.emissions_g for r in static.ticks[d * 24:(d + 1) * 24])
        print(f"{d:>4} {a:>11.1f} {s:>9.1f}")
    a, s = adaptive.total_emissions_g, static.total_emissions_g
    print(f"\nadaptive: {a:.1f} g ({adaptive.total_migrations} migrations)"
          f"  static: {s:.1f} g  ->  saved {1 - a / s:.1%}")
    print("\nfinal adaptive assignment:")
    for sid, (fl, node) in sorted(adaptive.final_assignment.items()):
        print(f"  {sid:>6} -> {node} ({fl})")


if __name__ == "__main__":
    main()
