"""Quickstart: the paper's Green-aware Constraint Generator end to end.

Runs the Online Boutique case study (Sect. 5.1): monitoring data ->
energy profiles -> green constraints -> explainability report ->
constraint-aware deployment plan, then one adaptive iteration after a
carbon-intensity shift (Scenario 3).

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import boutique
from repro.core.pipeline import GreenConstraintPipeline
from repro.core.scheduler import GreenScheduler, SchedulerConfig, plan_emissions
from repro.jax_cache import enable_persistent_cache


def emissions_of(plan, app, infra, comp, comm):
    assign = {p.service: (p.flavour, p.node) for p in plan.placements}
    return plan_emissions(app, infra, assign, comp, comm)


def main():
    enable_persistent_cache()
    # ---- iteration 1: Scenario 1 (Europe) --------------------------------
    app, infra, mon = boutique.scenario(1)
    pipe = GreenConstraintPipeline()
    out = pipe.run(app, infra, mon)

    print("=== Green-aware constraints (Prolog dialect) ===")
    print(out.prolog)
    print("\n=== Explainability Report (first entry) ===")
    print(out.report.entries[0])

    # one PlacementProblem per iteration — the single planner input; both
    # scheduler profiles share it (and its cached lowering)
    problem = pipe.problem_for(out)
    app_e, infra_e = out.app, out.infra
    comp, comm = out.computation, out.communication
    green = GreenScheduler(SchedulerConfig.green()).plan(problem).plan
    base = GreenScheduler(SchedulerConfig.baseline()).plan(problem).plan
    e_g = emissions_of(green, app_e, infra_e, comp, comm)
    e_b = emissions_of(base, app_e, infra_e, comp, comm)
    print("\n=== Deployment plan (green) ===")
    for p in green.placements:
        print(f"  {p.service:<16} [{p.flavour:<6}] -> {p.node}")
    print(f"\nemissions: baseline {e_b:.0f} g -> green {e_g:.0f} g "
          f"({100 * (1 - e_g / e_b):.1f}% saved)")

    # ---- iteration 2: France degrades (Scenario 3) ------------------------
    app3, infra3, mon3 = boutique.scenario(3)
    out3 = pipe.run(app3, infra3, mon3)  # same pipeline: KB carries over
    print("\n=== After carbon shift (France 16 -> 376 gCO2eq/kWh) ===")
    print(out3.prolog)


if __name__ == "__main__":
    main()
