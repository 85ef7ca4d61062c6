"""Smoke run of the main path on a TPU, through the normal entry points.

  python chip_smoke.py             # one chip: planner, continuum loop,
                                   # Monte Carlo, fleet
  python chip_smoke.py --chips 4   # the fleet planner sharded over four
                                   # chips against the same fleet on one

Phases on one chip, at the repo's own large cluster sizes:

  (a) device     the first jax device must be a TPU;
  (b) planner    ``GreenScheduler.plan`` on a 1000 x 200 problem (dense
                 backend) and a 2000 x 200 one (sparse backend), each
                 also planned on the CPU backend in this process, once
                 with the benchmark's values and once with every value
                 rounded to a multiple of 1/16; the ``ReferenceScheduler``
                 check of the equivalence tests;
  (c) continuum  96 services x 48 nodes for 48 ticks, eager ``run``
                 against ``run_scanned``, then ``run_scanned`` for 24
                 ticks at 1000 services x 201 nodes;
  (d) carbon     ``monte_carlo_emissions`` over 9 carbon realities;
  (e) fleet      ``plan_many`` on 1000 apps x 50 services over 200
                 shared nodes, uncoupled and waterfilled.

Decisions (placements, flavours, nodes, switches, migrations) must be
identical between the paths compared, and emissions must agree to a
relative 1e-9.  One tolerance: v5e emulates f64, and its multiply, add
and exp are not correctly rounded (they differ from the CPU's by up to
about 1e-14 relative), so the local search can break a near-tie between
two moves differently on the chip and on the CPU and then end in
another local optimum.  Where the arithmetic is exact (the rounded
values) the TPU plan must equal the CPU plan bit for bit.  With the
benchmark's values the two plans must have the same feasibility and
skipped services, and each must be a local optimum of the other
backend's search: warm-started from it, the other backend moves
nothing.  Every phase prints its wall time, the XLA compiles it
ran with their time, and its persistent compile-cache hits.  The last
line of stdout is ``{"ok": true, "device": {...}}`` and is printed only
when every check passed; off the chip the script exits non-zero.

Everything runs in this one process: a chip belongs to one process at a
time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
EMISSIONS_RTOL = 1e-9
DYADIC_STEP = 1.0 / 16     # values on this grid keep the planner exact
START = 24

# Cluster sizes: the repo's own large points (benchmarks/).
PLANNER_SIZES = (("dense", 1000, 200), ("sparse", 2000, 200))
REFERENCE_SIZE = (200, 100)             # services, nodes
CONTINUUM = (96, 16, 48)                # services, nodes per region, ticks
CONTINUUM_LARGE = (1000, 67, 24)
MC_REALITIES = 9
FLEET = (1000, 50, 200)                 # apps, services per app, nodes
FLEET_SEQUENTIAL_APPS = 8


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_diff(a, b) -> float:
    a = np.asarray(a, dtype=float)
    b = np.asarray(b, dtype=float)
    both_inf = np.isinf(a) & np.isinf(b) & (np.sign(a) == np.sign(b))
    a, b = np.where(both_inf, 0.0, a), np.where(both_inf, 0.0, b)
    scale = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-300)
    return float(np.max(np.abs(a - b) / scale, initial=0.0))


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# per-phase compile accounting (jax.monitoring events)
# ---------------------------------------------------------------------------


class CompileCounter:
    """XLA backend compiles, their seconds, and persistent-cache hits."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def install(self, jax) -> None:
        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


COUNTER = CompileCounter()
PHASES = []


@contextmanager
def phase(name: str):
    c0, s0, h0 = COUNTER.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, s1, h1 = COUNTER.snapshot()
    row = {"phase": name, "wall_s": wall, "compiles": c1 - c0,
           "compile_s": s1 - s0, "cache_hits": h1 - h0}
    PHASES.append(row)
    log(f"# phase {name}: wall {wall} s, {c1 - c0} compiles in "
        f"{s1 - s0} s, {h1 - h0} persistent-cache hits")


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def same_plan(a, b, what: str) -> None:
    """Two PlanResults: identical decisions, emissions to EMISSIONS_RTOL."""
    pa, pb = a.plans[0], b.plans[0]
    check(pa.feasible == pb.feasible, f"{what}: feasibility differs")
    check(pa.skipped_services == pb.skipped_services,
          f"{what}: skipped services differ")
    for name, xa, xb in zip(("placed", "flavour", "node"), a.arrays(0),
                            b.arrays(0)):
        bad = np.flatnonzero(np.asarray(xa) != np.asarray(xb))
        check(bad.size == 0,
              f"{what}: {name} differs at {bad.size} services, first "
              f"service index {bad[:1].tolist()}")
    d = rel_diff(a.emissions_g, b.emissions_g)
    check(d <= EMISSIONS_RTOL, f"{what}: emissions differ by rel {d}")


def tick_decisions(result):
    return [(r.t, r.replanned, r.switched, r.migrations, r.restarts,
             r.warm_start_rejected, r.n_constraints, r.evicted,
             r.emergency, r.violations) for r in result.ticks]


def same_ticks(a, b, what: str) -> None:
    da, db = tick_decisions(a), tick_decisions(b)
    for ra, rb in zip(da, db):
        check(ra == rb, f"{what}: decisions part at tick {ra[0]}: "
                        f"{ra} vs {rb}")
    check(len(da) == len(db), f"{what}: tick counts differ")
    check(a.final_assignment == b.final_assignment,
          f"{what}: final assignments differ")
    for field in ("emissions_g", "migration_g", "expected_saving_g"):
        xa = [getattr(r, field) for r in a.ticks]
        xb = [getattr(r, field) for r in b.ticks]
        d = rel_diff(xa, xb)
        if field == "expected_saving_g":   # compared as the tests do
            d = float(np.max(np.abs(np.subtract(xa, xb)), initial=0.0))
        check(d <= EMISSIONS_RTOL, f"{what}: {field} differs by {d}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def dyadic(inputs):
    """``synth`` output with every float rounded to ``DYADIC_STEP``, so
    every sum and product the planner forms is exact on any backend."""
    from dataclasses import replace

    def q(x):
        return round(x / DYADIC_STEP) * DYADIC_STEP

    app, infra, comp, comm, cs = inputs
    nodes = tuple(replace(n, carbon=q(n.carbon),
                          cost_per_cpu_hour=q(n.cost_per_cpu_hour))
                  for n in infra.nodes)
    return (app, replace(infra, nodes=nodes),
            {k: q(v) for k, v in comp.items()},
            {k: q(v) for k, v in comm.items()},
            [replace(c, weight=q(c.weight)) for c in cs])


def cross_optimal(jax, problem, plans, devices, inputs, cfg,
                  what: str) -> None:
    """The tolerance for inexact inputs: the TPU and CPU plans may differ,
    but each must be a local optimum of the other backend's search —
    warm-started from it, the other backend's planner moves nothing —
    with the same feasibility and skipped services.  The objectives of
    both plans, evaluated on the host, are reported."""
    from repro.core.scheduler import GreenScheduler, reference_objective

    (pa, pb) = (r.plans[0] for r in plans)
    check(pa.feasible == pb.feasible, f"{what}: feasibility differs")
    check(pa.skipped_services == pb.skipped_services,
          f"{what}: skipped services differ")
    for src, dev, label in ((plans[0], devices[1], "tpu plan on cpu"),
                            (plans[1], devices[0], "cpu plan on tpu")):
        with jax.default_device(dev):
            again = GreenScheduler(cfg).plan(
                problem.with_warm_start(src.assignment(0)))
        same_plan(again, src, f"{what}: {label}")
    assign = [r.assignment(0) for r in plans]
    j = [reference_objective(*inputs, cfg, x) for x in assign]
    moved = sum(assign[0][s] != assign[1].get(s) for s in assign[0])
    log(f"# {what}: {moved} services placed differently, both plans "
        f"local optima on both backends; objective tpu {j[0]} cpu "
        f"{j[1]} (rel {rel_diff(j[0], j[1])}), emissions rel "
        f"{rel_diff(plans[0].emissions_g, plans[1].emissions_g)}")


def planner_phase(jax) -> None:
    from benchmarks.scheduler_scalability import synth
    from repro.core.problem import PlacementProblem
    from repro.core.scheduler import (
        GreenScheduler, ReferenceScheduler, SchedulerConfig,
        reference_objective)

    cfg = SchedulerConfig.green()
    cpu = jax.devices("cpu")[0]
    for backend, S, N in PLANNER_SIZES:
        raw = synth(S, N)
        for values, inputs in (("exact", dyadic(raw)), ("raw", raw)):
            problem = PlacementProblem.build(*inputs)
            check(problem.lowering.comm.kind == backend,
                  f"{S}x{N} lowered to {problem.lowering.comm.kind}, "
                  f"expected {backend}")
            name = f"planner.{backend}.{S}x{N}.{values}"
            with phase(f"{name}.tpu"):
                on_chip = GreenScheduler(cfg).plan(problem)
            with phase(f"{name}.tpu_warm"):
                GreenScheduler(cfg).plan(problem)
            with phase(f"{name}.cpu"), jax.default_device(cpu):
                on_cpu = GreenScheduler(cfg).plan(problem)
            check(on_chip.plans[0].feasible, f"{name}: plan infeasible")
            if values == "exact":
                same_plan(on_chip, on_cpu, f"{name} tpu vs cpu")
                log(f"# {name}: emissions "
                    f"{float(on_chip.emissions_g[0])} g, tpu == cpu")
            else:
                cross_optimal(jax, problem, (on_chip, on_cpu),
                              (jax.devices()[0], cpu), inputs, cfg,
                              f"{name} tpu vs cpu")

    S, N = REFERENCE_SIZE
    app, infra, comp, comm, cs = synth(S, N)
    with phase(f"planner.reference.{S}x{N}"):
        ref = ReferenceScheduler(cfg).plan(app, infra, comp, comm, cs)
        vec = GreenScheduler(cfg).plan(
            PlacementProblem.build(app, infra, comp, comm, cs)).plan
    check(vec.feasible == ref.feasible, "reference: feasibility differs")
    check(set(vec.skipped_services) == set(ref.skipped_services),
          "reference: skipped services differ")
    if ref.feasible:
        j = [reference_objective(
            app, infra, comp, comm, cs, cfg,
            {p.service: (p.flavour, p.node) for p in plan.placements})
            for plan in (ref, vec)]
        check(j[1] <= j[0] + 1e-9 * max(1.0, abs(j[0])),
              f"reference objective {j[0]} beats the planner's {j[1]}")
        log(f"# reference {S}x{N}: objective {j[1]} <= reference {j[0]}")


def continuum_phase() -> None:
    from benchmarks.continuum_loop import _carbon_planner, build_scenario
    from repro.continuum import (
        CarbonTrace, ContinuumRuntime, REGION_PRESETS, RuntimeConfig,
        WorkloadTrace, monte_carlo_emissions)
    from repro.core.pipeline import GreenConstraintPipeline

    def runtime(app, infra, ticks):
        return ContinuumRuntime(
            app, infra,
            CarbonTrace(REGION_PRESETS, hours=START + ticks + 25, seed=0),
            WorkloadTrace(app, seed=0),
            config=RuntimeConfig(scenarios=4, hysteresis_g=30.0),
            pipeline=GreenConstraintPipeline(), planner=_carbon_planner())

    n_services, per_region, ticks = CONTINUUM
    app, infra = build_scenario(n_services=n_services,
                                nodes_per_region=per_region)
    size = f"{len(app.services)}x{len(infra.nodes)}"
    with phase(f"continuum.eager.{size}.{ticks}t"):
        eager = runtime(app, infra, ticks).run(START, ticks)
    rt = runtime(app, infra, ticks)
    with phase(f"continuum.scanned.{size}.{ticks}t"):
        scanned = rt.run_scanned(START, ticks)
    check(not rt.scanned_fallbacks,
          f"run_scanned fell back: {rt.scanned_fallbacks}")
    with phase(f"continuum.scanned_warm.{size}.{ticks}t"):
        runtime(app, infra, ticks).run_scanned(START, ticks)
    same_ticks(eager, scanned, "continuum eager vs scanned")
    total = scanned.total_emissions_g
    log(f"# continuum {size}: {ticks} ticks, {total} g, "
        f"{sum(r.migrations for r in scanned.ticks)} migrations, "
        "eager == scanned")

    M = MC_REALITIES
    scales = np.linspace(0.7, 1.3, M)   # odd M: the middle one is x1.0
    with phase(f"monte_carlo.{size}.{ticks}t.M{M}"):
        totals, per_tick = monte_carlo_emissions(
            runtime(app, infra, ticks), START, ticks, scales)
    check(totals.shape == (M,) and per_tick.shape == (M, ticks),
          f"monte carlo shapes {totals.shape} {per_tick.shape}")
    check(bool(np.isfinite(totals).all()), "monte carlo totals not finite")
    d = rel_diff(totals[M // 2], total)
    check(d <= EMISSIONS_RTOL,
          f"monte carlo reality x1.0 differs from run_scanned by rel {d}")
    log(f"# monte carlo: totals {totals.tolist()} g; x1.0 == run_scanned")

    n_services, per_region, ticks = CONTINUUM_LARGE
    app, infra = build_scenario(n_services=n_services,
                                nodes_per_region=per_region)
    size = f"{len(app.services)}x{len(infra.nodes)}"
    rt = runtime(app, infra, ticks)
    with phase(f"continuum.scanned.{size}.{ticks}t"):
        big = rt.run_scanned(START, ticks)
    check(not rt.scanned_fallbacks,
          f"run_scanned fell back at {size}: {rt.scanned_fallbacks}")
    check(len(big.ticks) == ticks and np.isfinite(big.total_emissions_g),
          f"continuum {size}: bad result")
    log(f"# continuum {size}: {ticks} ticks, {big.total_emissions_g} g")


def build_fleet_problem(coupling: str):
    from benchmarks.fleet_scale import build_fleet
    from repro.fleet import FleetProblem

    apps = build_fleet(*FLEET)
    A = len(apps)
    return FleetProblem(
        apps=apps, names=tuple(f"tenant{i}" for i in range(A)),
        priority=tuple(float(A - i) for i in range(A)), coupling=coupling)


def fleet_scheduler():
    from repro.core.scheduler import GreenScheduler, SchedulerConfig

    return GreenScheduler(SchedulerConfig(
        emission_weight=0.25, local_search_rounds=2))


def same_fleet(a, b, what: str) -> None:
    check(a.A == b.A, f"{what}: app counts differ")
    for i, (ra, rb) in enumerate(zip(a.results, b.results)):
        same_plan(ra, rb, f"{what} app {i}")


def fleet_phase(devices) -> None:
    from dataclasses import replace

    from repro.fleet import plan_many

    sched = fleet_scheduler()
    fleet = build_fleet_problem("none")
    name = "x".join(map(str, FLEET))
    with phase(f"fleet.none.{name}"):
        unc = plan_many(fleet, sched, devices=devices)
    with phase(f"fleet.none.{name}.warm"):
        plan_many(fleet, sched, devices=devices)
    check(unc.stats.devices == len(devices), "fleet: wrong device count")
    with phase(f"fleet.sequential.{FLEET_SEQUENTIAL_APPS}apps"):
        seq = [sched.plan(p) for p in fleet.apps[:FLEET_SEQUENTIAL_APPS]]
    for i, r in enumerate(seq):
        same_plan(unc.results[i], r, f"fleet app {i} batched vs plan()")
    wf_fleet = replace(fleet, coupling="waterfill")
    with phase(f"fleet.waterfill.{name}"):
        wf = plan_many(wf_fleet, sched, devices=devices)
    check(wf.capacity.violations == 0,
          f"waterfill over-committed {wf.capacity.violations} nodes")
    log(f"# fleet: uncoupled {int(unc.feasible.sum())}/{unc.A} feasible, "
        f"{unc.capacity.violations} violated nodes; waterfill "
        f"{int(wf.feasible.sum())}/{wf.A} feasible, 0 violated nodes")


def sharded_fleet_phase(devices) -> None:
    from dataclasses import replace

    from repro.fleet import plan_many

    sched = fleet_scheduler()
    fleet = build_fleet_problem("none")
    name = "x".join(map(str, FLEET))
    for coupling in ("none", "price"):
        fp = replace(fleet, coupling=coupling)
        with phase(f"fleet.{coupling}.{name}.1dev"):
            one = plan_many(fp, sched, devices=devices[:1])
        with phase(f"fleet.{coupling}.{name}.{len(devices)}dev"):
            many = plan_many(fp, sched, devices=devices)
        with phase(f"fleet.{coupling}.{name}.{len(devices)}dev.warm"):
            plan_many(fp, sched, devices=devices)
        check(many.stats.sharded and many.stats.devices == len(devices),
              f"fleet {coupling}: not sharded over {len(devices)} devices "
              f"(sharded={many.stats.sharded}, "
              f"devices={many.stats.devices})")
        check(not one.stats.sharded, f"fleet {coupling}: 1 device sharded")
        same_fleet(one, many, f"fleet {coupling} 1 vs {len(devices)} dev")
        log(f"# fleet {coupling}: {len(devices)} devices == 1 device, "
            f"{int(many.feasible.sum())}/{many.A} feasible, "
            f"{many.capacity.violations} violated nodes, "
            f"{many.stats.price_rounds} price rounds")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the fleet planner sharded over four "
                         "chips, compared with one")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    visible = len(jax.devices())
    log(f"# device: platform {dev.platform}, kind {dev.device_kind}, "
        f"{visible} visible")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    if visible < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {visible} visible",
              file=sys.stderr)
        return 2
    devices = jax.devices()[:args.chips]

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.jax_cache import enable_persistent_cache

    enable_persistent_cache(report=log)
    COUNTER.install(jax)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            planner_phase(jax)
            continuum_phase()
            fleet_phase(devices)
        else:
            sharded_fleet_phase(devices)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    total = time.perf_counter() - t0
    compile_s = sum(p["compile_s"] for p in PHASES)
    log(f"# total: wall {total} s, {sum(p['compiles'] for p in PHASES)} "
        f"compiles in {compile_s} s, "
        f"{sum(p['cache_hits'] for p in PHASES)} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
