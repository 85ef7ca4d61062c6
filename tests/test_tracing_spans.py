"""The program's own spans on the Online Boutique: a lone tracer's tree on
the eager tick and on ``run_scanned``, the tiling of the planner call and
of the fused scan, the bytes counted at the program boundary, what a
detached runtime pays, and the name scopes the compiled programs carry."""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.configs.boutique import (
    EUROPE_CI,
    build_application,
    europe_infrastructure,
)
from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    RuntimeConfig,
    WhatIfPlanner,
    WorkloadTrace,
)
from repro.continuum import megaloop
from repro.continuum.traces import RegionProfile
from repro.core import scheduler
from repro.core.scheduler import GreenScheduler, SchedulerConfig
from repro.obs import REGISTRY, Observability, Tracer
from repro.obs import trace as trace_mod

START = 24
TICKS = 6
PLAN_CHILDREN = ["plan.prepare", "plan.dispatch", "plan.wait", "plan.fetch",
                 "plan.decode", "plan.price"]
TICK_CHILDREN = ["telemetry.ingest", "constraints", "lower.rebuild",
                 "scenarios", "plan.evaluate", "switch", "account"]


def _runtime(**kw):
    app, infra = build_application(), europe_infrastructure()
    regions = {c: RegionProfile(ci, 0.4 * ci, 12.0 + i, 0.05 * ci)
               for i, (c, ci) in enumerate(sorted(EUROPE_CI.items()))}
    return ContinuumRuntime(
        app, infra, CarbonTrace(regions, hours=START + TICKS + 25, seed=3),
        WorkloadTrace(app, seed=3),
        config=RuntimeConfig(scenarios=4, hysteresis_g=30.0),
        planner=WhatIfPlanner(GreenScheduler(
            SchedulerConfig(emission_weight=1.0))), **kw)


def _children(tr, span):
    return [s for s in tr.spans if s.parent == span.span_id]


def _assert_tiles(parent, kids, cover=0.9):
    """``kids`` in order, disjoint, inside ``parent``, covering ``cover``
    of it."""
    assert kids
    assert kids[0].t0 >= parent.t0 and kids[-1].t1 <= parent.t1
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0
    assert all(k.t1 >= k.t0 for k in kids)
    assert sum(k.duration_s for k in kids) >= cover * parent.duration_s


@pytest.fixture(scope="module")
def eager():
    tr = Tracer()
    rt = _runtime(tracer=tr)
    res = rt.run(START, TICKS)
    return rt, tr, res


@pytest.fixture(scope="module")
def scanned():
    tr = Tracer()
    rt = _runtime(tracer=tr)
    rt.run(START, 2)            # an incumbent, so the scan warm-starts
    tr.clear()
    res = rt.run_scanned(START + 2, TICKS)
    assert rt.last_scanned_fallback is None
    return rt, tr, res


def test_lone_tracer_builds_the_tick_tree(eager):
    rt, tr, res = eager
    ticks = tr.by_name("tick")
    assert len(ticks) == TICKS and rt.obs is None
    for tick, rec in zip(ticks, res.ticks):
        assert tick.parent is None and rec.replanned
        assert [s.name for s in _children(tr, tick)] == TICK_CHILDREN
        (ev,) = [s for s in _children(tr, tick)
                 if s.name == "plan.evaluate"]
        assert [s.name for s in _children(tr, ev)] == PLAN_CHILDREN


def test_lone_tracer_builds_the_scan_tree(scanned):
    _, tr, _ = scanned
    (root,) = tr.by_name("run_scanned")
    kids = _children(tr, root)
    assert [s.name for s in kids] == ["scan.stage", "scan.fused",
                                      "scan.commit"]
    stage, fused, commit = kids
    staged = [s.name for s in _children(tr, stage)]
    assert staged == ["scan.stage.ingest", "scan.stage.engine",
                      "scan.stage.lower", "scan.stage.engine",
                      "scan.stage.ingest"] * TICKS
    assert stage.attrs["replanned"] == TICKS
    assert sum(stage.attrs[p] for p in ("cache_hit", "delta", "full")) \
        == TICKS
    assert [s.name for s in _children(tr, fused)] == ["scan.dispatch",
                                                      "scan.wait"]
    assert [s.name for s in _children(tr, commit)] == ["scan.fetch"]
    _assert_tiles(stage, _children(tr, stage), cover=0.5)


def test_planner_children_tile_plan_evaluate(eager):
    _, tr, _ = eager
    for ev in tr.by_name("plan.evaluate"):
        _assert_tiles(ev, _children(tr, ev))


def test_scan_children_tile_the_fused_scan(scanned):
    _, tr, _ = scanned
    (fused,) = tr.by_name("scan.fused")
    _assert_tiles(fused, _children(tr, fused))
    (commit,) = tr.by_name("scan.commit")
    (fetch,) = _children(tr, commit)
    assert commit.t0 == fetch.t0 <= fetch.t1 <= commit.t1


def test_lone_tracer_compiles_the_program_without_metrics(monkeypatch):
    seen = {"flags": [], "sigs": []}
    scan_fn, record = megaloop._scan_fn, megaloop.COMPILE_CACHE.record

    def spy_fn(kind, with_metrics=False, with_watch=False):
        seen["flags"].append((with_metrics, with_watch))
        return scan_fn(kind, with_metrics, with_watch)

    def spy_record(sig, secs):
        seen["sigs"].append(sig)
        return record(sig, secs)

    monkeypatch.setattr(megaloop, "_scan_fn", spy_fn)
    monkeypatch.setattr(megaloop.COMPILE_CACHE, "record", spy_record)
    tr = Tracer()
    rt = _runtime(tracer=tr)
    rt.run_scanned(START, TICKS)
    assert tr.by_name("run_scanned")
    assert seen["flags"] == [(False, False)]
    # the commit's signature ends in its metrics flag
    assert [s[-1] for s in seen["sigs"]] == [False]


def test_detached_tick_builds_no_span_and_no_histogram(monkeypatch):
    rt = _runtime()
    rt.run(START, 1)
    built = []

    class CountingSpan(trace_mod.Span):
        def __init__(self, *a, **kw):
            built.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "Span", CountingSpan)
    before = {k: (h.count, h.sum) for k, h in REGISTRY.histograms().items()}
    rt.run(START + 1, 3)
    after = {k: (h.count, h.sum) for k, h in REGISTRY.histograms().items()}
    assert built == [] and after == before
    # a bundle keeps the stage split the README documents
    obs = Observability()
    rt.obs = obs
    rt.tick(START + 4)
    assert obs.registry.histogram("stage.plan_s").count == 1
    assert obs.registry.histogram("stage.price_s").count == 1
    assert len(built) == len(obs.tracer.spans) > 0


def test_transfer_counts_are_the_bytes_of_the_calls(monkeypatch):
    calls = []
    batched = scheduler._batched_planner

    def spy_planner(sig):
        fn = batched(sig)

        def call(*args):
            out = fn(*args)
            calls.append(("plan", args, out))
            return out
        return call

    scan_fn = megaloop._scan_fn

    def spy_scan(*a, **kw):
        fn = scan_fn(*a, **kw)

        def call(*args):
            out = fn(*args)
            calls.append(("scan", args, out))
            return out
        return call

    monkeypatch.setattr(scheduler, "_batched_planner", spy_planner)
    monkeypatch.setattr(megaloop, "_scan_fn", spy_scan)
    tr = Tracer()
    rt = _runtime(tracer=tr)
    rt.run(START, 2)
    rt.run_scanned(START + 2, TICKS)
    plans = [c for c in calls if c[0] == "plan"]
    assert len(plans) == 2 and len(tr.by_name("plan.dispatch")) == 2
    for (_, args, out), disp, fetch in zip(
            plans, tr.by_name("plan.dispatch"), tr.by_name("plan.fetch")):
        # every argument is an array, and there are at most three of them
        sent = [a for a in args if isinstance(a, np.ndarray)]
        assert len(sent) == len(args) <= 3
        assert disp.attrs["args"] == len(sent)
        assert disp.attrs["h2d_bytes"] == sum(a.nbytes for a in sent) > 0
        fetched = jax.tree_util.tree_leaves(out)
        assert fetch.attrs["outs"] == len(fetched) == 1
        assert fetch.attrs["d2h_bytes"] == sum(
            np.asarray(a).nbytes for a in fetched) > 0
    ((_, args, out),) = [c for c in calls if c[0] == "scan"]
    sent = [a for a in jax.tree_util.tree_leaves(args)
            if hasattr(a, "nbytes")]
    (disp,), (fetch,) = tr.by_name("scan.dispatch"), tr.by_name("scan.fetch")
    assert disp.attrs["args"] == len(sent)
    assert disp.attrs["h2d_bytes"] == sum(a.nbytes for a in sent)
    assert fetch.attrs["d2h_bytes"] == sum(
        np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(out))


def _op_names(hlo_text):
    return [ln.split('op_name="', 1)[1].split('"', 1)[0]
            for ln in hlo_text.splitlines() if 'op_name="' in ln]


def _has_scope(names, scope):
    """A name-stack element ``scope``, bare or under a transform
    (``vmap(greedy)``)."""
    pat = re.compile(rf"(^|[/(]){scope}[/)]")
    return any(pat.search(n) for n in names)


@pytest.fixture(scope="module")
def program_args():
    """The arguments of one planner call and of one fused scan call."""
    calls = {}
    batched, scan_fn = scheduler._batched_planner, megaloop._scan_fn

    def spy(name, fn):
        def call(*args):
            calls.setdefault(name, args)
            return fn(*args)
        return call

    scheduler._batched_planner = lambda sig: spy(
        ("plan", sig), batched(sig))
    megaloop._scan_fn = lambda kind, *flags: spy(
        ("scan", kind), scan_fn(kind, *flags))
    try:
        rt = _runtime()
        rt.run(START, 1)
        rt.run_scanned(START + 1, 4)
    finally:
        scheduler._batched_planner, megaloop._scan_fn = batched, scan_fn
    return {name: (key, args) for (name, key), args in calls.items()}


def test_compiled_programs_carry_the_scopes(program_args):
    sig, args = program_args["plan"]
    with jax.enable_x64(True):
        text = scheduler._batched_planner(sig).lower(
            *args).compile().as_text()
    names = _op_names(text)
    assert any(n.startswith("jit(green_planner)/") for n in names)
    for scope in ("greedy", "local_search"):
        assert _has_scope(names, scope), scope
    kind, args = program_args["scan"]
    with jax.enable_x64(True):
        text = megaloop._scan_fn(kind).lower(*args).compile().as_text()
    names = _op_names(text)
    assert any(n.startswith("jit(fused_tick_scan)/") for n in names)
    for scope in ("warm_start", "plan", "price", "switch", "account",
                  "greedy", "local_search"):
        assert _has_scope(names, scope), scope


def test_plans_are_the_same_without_the_scopes(monkeypatch):
    def run():
        rt = _runtime()
        eager = rt.run(START, 3)
        res = rt.last_result
        scanned = rt.run_scanned(START + 3, 4)
        return ([(r.switched, r.migrations, r.emissions_g)
                 for r in eager.ticks + scanned.ticks],
                [p.placements for p in res.plans],
                scanned.final_assignment)

    scoped = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(scheduler, "_PLAN_SINGLE_CACHE", {})
    monkeypatch.setattr(scheduler, "_PLAN_BATCH_CACHE", {})
    monkeypatch.setattr(megaloop, "_SCAN_CACHE", {})
    assert run() == scoped
