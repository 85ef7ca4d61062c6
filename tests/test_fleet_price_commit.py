"""The fleet commit keeps capacity whatever the coupling.

* **contended price fleet** — Boutique tenants on a pool too small for
  all of them where they want to be: the price rounds leave machines
  over-committed, yet the committed fleet never is; switches that do not
  fit hold, and first rollouts that do not fit are planned by the
  waterfill program into the capacity left.
* **hand-worked** — two tenants on two 4-vCPU machines, one clean and
  one dirty: the first rollout puts the first tenant on the clean
  machine and repairs the second onto the dirty one; next tick the
  second tenant wants the clean machine and holds.
* **uncontended** — where the gates' switches fit together, the rule
  commits exactly what adopting every switch commits.
* **devices** — ``FleetRuntime(devices=...)`` reaches ``plan_many``: one
  device of four plans unsharded.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.boutique import EUROPE_CI, build_application
from repro.continuum import CarbonTrace, RuntimeConfig, WorkloadTrace
from repro.continuum.traces import RegionProfile
from repro.core.types import (
    Application,
    Flavour,
    FlavourRequirements,
    Infrastructure,
    Node,
    NodeCapabilities,
    Service,
)
from repro.fleet import FleetApp, FleetRuntime

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _pool(per_region=2, cpu=12.0, ram=24.0):
    return Infrastructure("pool", tuple(
        Node(f"{c}-{k}", region=c, cost_per_cpu_hour=0.1,
             capabilities=NodeCapabilities(cpu=cpu, ram_gb=ram))
        for c in sorted(EUROPE_CI) for k in range(per_region)))


def _carbon(hours=48):
    regions = {c: RegionProfile(ci, 0.4 * ci, 12.0 + i, 0.05 * ci)
               for i, (c, ci) in enumerate(sorted(EUROPE_CI.items()))}
    return CarbonTrace(regions, hours=hours, seed=3)


def _boutique_fleet(n, coupling, infra, **kw):
    app = build_application()
    apps = [FleetApp(f"t{i:02d}", app,
                     WorkloadTrace(app, seed=i, peak_hour=float(i % 24)))
            for i in range(n)]
    return FleetRuntime(apps, infra, _carbon(),
                        config=RuntimeConfig(horizon_h=6, hysteresis_g=30.0),
                        coupling=coupling, max_batch=8, **kw)


def _summed_loads_fit(frt, frec):
    """The committed fleet's per-node loads, summed from each tenant's
    assignment, against the node capacities."""
    infra = frt.infra
    cpu = {n.node_id: 0.0 for n in infra.nodes}
    ram = dict(cpu)
    app = frt.apps[0].app
    req = {(s.component_id, f.name): f.requirements
           for s in app.services for f in s.flavours}
    for fa in frt.apps:
        for sid, (fl, nid) in (frt.runtime(fa.name).current or {}).items():
            cpu[nid] += req[sid, fl].cpu
            ram[nid] += req[sid, fl].ram_gb
    return all(cpu[n.node_id] <= n.capabilities.cpu
               and ram[n.node_id] <= n.capabilities.ram_gb
               for n in infra.nodes)


@pytest.mark.parametrize("coupling", ["price", "waterfill"])
def test_contended_fleet_never_overcommits(coupling):
    frt = _boutique_fleet(16, coupling, _pool())
    held = repaired = residual = 0
    for t in range(24, 30):
        frec = frt.tick(t)
        assert frec.capacity.violations == 0
        assert _summed_loads_fit(frt, frec)
        assert frec.refused == ()
        held += len(frec.held)
        repaired += len(frec.repaired)
        residual += frec.planned_capacity.violations
    assert frt.placement_violations == []
    if coupling == "price":
        # the rounds leave machines over-committed, the commit does not
        assert residual > 0 and held > 0 and repaired > 0


def _two_machine_fleet(coupling="none"):
    infra = Infrastructure("two", (
        Node("a", region="clean", cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=4.0, ram_gb=8.0)),
        Node("b", region="dirty", cost_per_cpu_hour=0.5,
             capabilities=NodeCapabilities(cpu=4.0, ram_gb=8.0))))
    carbon = CarbonTrace({"clean": RegionProfile(20.0, 0.0, 0.0, 0.0),
                          "dirty": RegionProfile(400.0, 0.0, 0.0, 0.0)},
                         hours=8, seed=0)
    small = (Flavour("small", FlavourRequirements(cpu=2.0, ram_gb=2.0)),)
    app = Application("pair", (Service("s0", flavours=small),
                               Service("s1", flavours=small)), ())
    apps = [FleetApp(f"t{i}", app, WorkloadTrace(app, seed=i, noise=0.0))
            for i in range(2)]
    return FleetRuntime(apps, infra, carbon,
                        config=RuntimeConfig(horizon_h=4), coupling=coupling)


@pytest.mark.parametrize("coupling", ["none", "price"])
def test_first_rollout_repairs_then_switch_holds_by_hand(coupling):
    frt = _two_machine_fleet(coupling)
    on_a = {"s0": ("small", "a"), "s1": ("small", "a")}
    on_b = {"s0": ("small", "b"), "s1": ("small", "b")}
    # tick 0: both tenants plan onto the clean machine, which holds one
    # of them; t0 comes first, so t1 is planned into what is left: b
    frec = frt.tick(0)
    assert frt.runtime("t0").current == on_a
    assert frt.runtime("t1").current == on_b
    assert frec.repaired == ("t1",) and frec.held == ()
    assert frec.records["t1"].switched and frec.records["t1"].migrations == 2
    np.testing.assert_array_equal(frec.capacity.cpu_load, [4.0, 4.0])
    # tick 1: t1's candidate moves to a and saves far more than its two
    # migrations and the hysteresis, but a is full: it holds
    frec = frt.tick(1)
    assert frt.last_result.result("t1").assignment(0) == on_a
    assert frec.held == ("t1",) and frec.repaired == ()
    assert not frec.records["t1"].switched
    assert frec.records["t1"].expected_saving_g > 30.0
    assert frt.runtime("t1").current == on_b
    np.testing.assert_array_equal(frec.capacity.cpu_load, [4.0, 4.0])


def _adopt_every_switch(self, fleet, problems, cands, decisions, force):
    """The commit before the capacity rule: every gate's switch."""
    return ({i for i, d in enumerate(decisions) if d is not None
             and d.switch}, [], [], ())


@pytest.mark.parametrize("coupling", ["none", "price", "waterfill"])
def test_uncontended_fleet_commits_as_before(coupling, monkeypatch):
    runs = []
    for rule in (True, False):
        frt = _boutique_fleet(4, coupling, _pool(cpu=64.0, ram=128.0))
        if not rule:
            monkeypatch.setattr(FleetRuntime, "_fit", _adopt_every_switch)
        recs = [frt.tick(t) for t in range(24, 28)]
        monkeypatch.undo()
        if rule:
            assert all(r.held == r.repaired == r.refused == () for r in recs)
        runs.append((
            [{n: (r.switched, r.migrations, r.restarts, r.migration_g,
                  r.emissions_g) for n, r in fr.records.items()}
             for fr in recs],
            {fa.name: frt.runtime(fa.name).current for fa in frt.apps}))
    assert runs[0] == runs[1]


_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax
from test_fleet_price_commit import _boutique_fleet, _pool
out = {{}}
for label, devices in (("one", jax.devices()[:1]), ("all", None)):
    frt = _boutique_fleet(6, "price", _pool(), devices=devices)
    recs = [frt.tick(t) for t in (24, 25)]
    out[label] = {{
        "devices": [r.plan_stats.devices for r in recs],
        "sharded": [r.plan_stats.sharded for r in recs],
        "current": {{fa.name: sorted(frt.runtime(fa.name).current.items())
                    for fa in frt.apps}}}}
print(json.dumps(out))
"""


def test_runtime_devices_reach_the_planner_subprocess():
    code = _DEVICES.format(
        src=os.path.abspath(SRC),
        tests=os.path.abspath(os.path.dirname(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["one"]["devices"] == [1, 1]
    assert out["one"]["sharded"] == [False, False]
    assert out["all"]["devices"] == [4, 4]
    assert out["all"]["sharded"] == [True, True]
    # the app axis split over four devices plans what one device plans
    assert out["one"]["current"] == out["all"]["current"]
