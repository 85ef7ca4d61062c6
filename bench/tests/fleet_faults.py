"""Faults planted under the fleet cell's timed path, registered beside
``faults.FAULTS`` by the fleet's tests: each wraps one function of the
program for the length of a ``faults.plant(name)`` block."""
from __future__ import annotations

import json

from . import faults


def _plans_swapped(orig):
    """The first and the last tenant's candidates swapped where the price
    rounds produce them."""
    def plan_many(fleet, *args, **kwargs):
        res = orig(fleet, *args, **kwargs)
        if fleet.coupling == "price" and res.A > 1:
            r, em = res.results, res.emissions_g
            r[0], r[-1] = r[-1], r[0]
            em[0], em[-1] = em[-1], em[0]
        return res
    return plan_many


def _no_capacity_rule(orig):
    """Every gate's switch committed, as on an emergency tick, whether or
    not the machines can hold them."""
    def fit(self, fleet, problems, cands, decisions, force):
        return orig(self, fleet, problems, cands, decisions, True)
    return fit


def _prices_doubled(orig):
    """The shadow prices folded into the penalties at twice their value."""
    def fold(prep, lam_cpu, lam_ram, gp, gp_eff):
        return orig(prep, 2.0 * lam_cpu, 2.0 * lam_ram, gp, gp_eff)
    return fold


def _held_as_switched(orig):
    """Tenants the capacity held reported as switched."""
    def tick(self, t):
        frec = orig(self, t)
        for name in frec.held:
            frec.records[name].switched = True
        return frec
    return tick


def _switch_uncharged(orig):
    """Tenants that switched reported with no migration charge."""
    def tick(self, t):
        frec = orig(self, t)
        for rec in frec.records.values():
            if rec.switched:
                rec.migration_g = 0.0
        return frec
    return tick


FLEET_FAULTS = {
    "fleet.plans_swapped": ("repro.fleet.runtime", "plan_many",
                            _plans_swapped),
    "fleet.overcommitted": ("repro.fleet.runtime", "FleetRuntime._fit",
                            _no_capacity_rule),
    "fleet.wrong_prices": ("repro.fleet.planner", "_price_penalties",
                           _prices_doubled),
    "fleet.held_as_switched": ("repro.fleet.runtime", "FleetRuntime.tick",
                               _held_as_switched),
    "fleet.switch_uncharged": ("repro.fleet.runtime", "FleetRuntime.tick",
                               _switch_uncharged),
}

RUN = """
from bench.tests import faults, fleet_faults
faults.FAULTS.update(fleet_faults.FLEET_FAULTS)
""" + faults.RUN


def readings(root, workload: str, names, seed: int, overrides) -> dict:
    """``faults.readings`` with the fleet's faults registered."""
    from .cells import run_python

    case = {"workload": workload, "faults": list(names), "seed": seed,
            "overrides": overrides}
    proc = run_python(root, ["-c", RUN, json.dumps(case)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
