"""Monte Carlo over whole adaptive traces: one vmap'd megaloop call.

The scanned continuum loop stages a trace once and rolls it with a
single ``jit(lax.scan)``; ``monte_carlo_emissions`` then ``vmap``s that
program over a batch of carbon realities (multiplicative perturbations
of the recorded/forecast carbon intensity).  Every sample replays the
FULL adaptive loop — replanning, hysteresis, switching, migration
charges — under its own carbon world, so the spread is the real
sensitivity of the closed-loop system, not of a frozen plan.

Prints the emissions distribution of a 2-day trace under ±30% carbon
scenarios, next to the deterministic (scale = 1.0) trace.  With
``--dump PATH`` the deterministic trace is also rolled once (fused
scan, full observability) and written as a ContinuumResult JSONL that
``benchmarks.make_tables`` renders into a green-audit section.

  PYTHONPATH=src python examples/monte_carlo_traces.py [--dump PATH]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    REGION_PRESETS,
    RuntimeConfig,
    WhatIfPlanner,
    WorkloadTrace,
)
from repro.continuum.megaloop import monte_carlo_emissions
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

START, TICKS = 24, 48


def build():
    services = tuple(
        Service(f"svc{i}", flavours=(
            Flavour("large", FlavourRequirements(cpu=2.0, ram_gb=4.0)),
            Flavour("small", FlavourRequirements(cpu=1.0, ram_gb=2.0)),
        )) for i in range(10))
    links = tuple(CommunicationLink(f"svc{i}", f"svc{(i + 1) % 10}")
                  for i in range(0, 10, 2))
    app = Application("mc-demo", services, links)
    nodes = tuple(
        Node(f"{r}-{k}", region=r, cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=5.0, ram_gb=24.0))
        for r in ("solar-south", "wind-north", "coal-east")
        for k in range(2))
    return app, Infrastructure("mc-demo", nodes)


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", metavar="PATH", default=None,
                    help="write the deterministic trace as a "
                         "ContinuumResult JSONL (continuum-result/v1)")
    args = ap.parse_args()
    app, infra = build()
    runtime = ContinuumRuntime(
        app, infra,
        CarbonTrace(REGION_PRESETS, hours=START + TICKS + 25, seed=0),
        WorkloadTrace(app, seed=0),
        config=RuntimeConfig(scenarios=4, hysteresis_g=30.0),
        planner=WhatIfPlanner(
            GreenScheduler(SchedulerConfig(emission_weight=1.0))))

    # 21 carbon realities from 30% cleaner to 30% dirtier, one vmap call
    scales = np.linspace(0.7, 1.3, 21)
    totals, per_tick = monte_carlo_emissions(
        runtime, START, TICKS, ci_scales=scales)

    det = totals[np.argmin(np.abs(scales - 1.0))]
    print(f"# {len(scales)} carbon realities x {TICKS} ticks "
          f"(one vmap(jit(lax.scan)) call)")
    print(f"deterministic trace : {det:10.1f} gCO2eq")
    print(f"mean / std          : {totals.mean():10.1f} / "
          f"{totals.std():.1f} gCO2eq")
    print(f"p05 .. p95          : {np.percentile(totals, 5):10.1f} .. "
          f"{np.percentile(totals, 95):.1f} gCO2eq")
    # the adaptive loop is sub-linear in carbon scale: when the whole
    # grid gets dirtier it shifts more load to the cleanest regions
    lo, hi = totals[0], totals[-1]
    print(f"0.7x / 1.3x carbon  : {lo:10.1f} / {hi:.1f} gCO2eq "
          f"({hi / det - 1.0:+.1%} at +30% CI)")
    worst = per_tick.max(axis=0)
    print(f"worst-case tick     : {worst.max():10.1f} gCO2eq "
          f"(tick {int(worst.argmax())})")

    if args.dump:
        from repro.obs import Observability
        runtime.obs = Observability()
        result = runtime.run_scanned(START, TICKS)
        result.to_jsonl(args.dump)
        print(f"wrote {args.dump} ({len(result.ticks)} ticks, "
              f"schema continuum-result/v1)")


if __name__ == "__main__":
    main()
