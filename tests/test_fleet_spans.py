"""The fleet tick's own spans: a lone tracer's tree on a contended,
price-coupled Boutique fleet, the tiling of ``fleet.tick``,
``fleet.ingest``, ``fleet.plan`` and each ``fleet.round``, the counters
on them, and a detached runtime that records nothing and commits the
same."""
import numpy as np
import pytest

from repro.obs import Observability, Tracer

from test_fleet_price_commit import _boutique_fleet, _pool

TICKS = range(24, 28)
TICK_CHILDREN = ["fleet.ingest", "fleet.plan", "fleet.commit"]
INGEST_CHILDREN = ["fleet.telemetry", "fleet.constraints", "fleet.lower"]
PLAN_COUNTERS = {"apps", "padded_apps", "calls", "devices", "price_rounds",
                 "overcommitted"}
COMMIT_COUNTERS = {"switched", "held", "repaired", "refused"}


def _children(tr, span):
    return [s for s in tr.spans if s.parent == span.span_id]


def _assert_tiles(parent, kids, cover):
    """``kids`` in order, disjoint, inside ``parent``, covering ``cover``
    of it."""
    assert kids
    assert kids[0].t0 >= parent.t0 and kids[-1].t1 <= parent.t1
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0
    assert all(k.t1 >= k.t0 for k in kids)
    assert sum(k.duration_s for k in kids) >= cover * parent.duration_s


def _run(coupling="price", **kw):
    frt = _boutique_fleet(16, coupling, _pool(), **kw)
    return frt, [frt.tick(t) for t in TICKS]


@pytest.fixture(scope="module")
def traced():
    tr = Tracer()
    frt, recs = _run(tracer=tr)
    return frt, tr, recs


def test_lone_tracer_builds_the_fleet_tree(traced):
    frt, tr, recs = traced
    ticks = tr.by_name("fleet.tick")
    assert len(ticks) == len(TICKS) and frt.obs is None
    for tick, rec in zip(ticks, recs):
        assert tick.parent is None and tick.attrs["t"] == rec.t
        assert [s.name for s in _children(tr, tick)] == TICK_CHILDREN
        ingest, plan, _ = _children(tr, tick)
        assert [s.name for s in _children(tr, ingest)] == INGEST_CHILDREN
        names = [s.name for s in _children(tr, plan)]
        rounds = rec.plan_stats.price_rounds
        assert names == (["fleet.prepare"] + ["fleet.round"] * rounds
                         + ["fleet.finalize"])
        for rnd in _children(tr, plan)[1:-1]:
            kids = [s.name for s in _children(tr, rnd)]
            calls = (len(kids) - 1) // 4
            # each call's chunk folded and stacked just before it
            assert kids == (["fleet.fold", "fleet.dispatch", "fleet.wait",
                             "fleet.fetch"] * calls + ["fleet.loads"])
            # 16 apps in chunks of max_batch 8
            assert calls == 2


def test_fleet_children_tile_their_parents(traced):
    _, tr, _ = traced
    for tick in tr.by_name("fleet.tick"):
        _assert_tiles(tick, _children(tr, tick), cover=0.95)
    for ingest in tr.by_name("fleet.ingest"):
        _assert_tiles(ingest, _children(tr, ingest), cover=0.9)
    for plan in tr.by_name("fleet.plan"):
        _assert_tiles(plan, _children(tr, plan), cover=0.95)
    for rnd in tr.by_name("fleet.round"):
        _assert_tiles(rnd, _children(tr, rnd), cover=0.9)


def test_fleet_counters(traced):
    _, tr, recs = traced
    for plan, rec in zip(tr.by_name("fleet.plan"), recs):
        assert PLAN_COUNTERS <= set(plan.attrs)
        st = rec.plan_stats
        assert plan.attrs["apps"] == 16 and plan.attrs["devices"] == 1
        assert plan.attrs["calls"] == st.calls == 2 * st.price_rounds
        assert plan.attrs["price_rounds"] == st.price_rounds
        assert plan.attrs["overcommitted"] \
            == rec.planned_capacity.violations
    # the pool is contended: rounds run out with machines over-committed
    assert any(p.attrs["overcommitted"] for p in tr.by_name("fleet.plan"))
    for d in tr.by_name("fleet.dispatch"):
        assert d.attrs["h2d_bytes"] > 0
    for f in tr.by_name("fleet.fetch"):
        assert f.attrs["d2h_bytes"] > 0
    for commit, rec in zip(tr.by_name("fleet.commit"), recs):
        assert set(commit.attrs) == COMMIT_COUNTERS
        assert commit.attrs["held"] == len(rec.held)
        assert commit.attrs["repaired"] == len(rec.repaired)
        assert commit.attrs["refused"] == len(rec.refused)
        assert commit.attrs["switched"] == sum(
            r.switched for r in rec.records.values())
    assert sum(c.attrs["held"] for c in tr.by_name("fleet.commit")) > 0


def test_bundle_tracer_takes_the_lone_tracers_place():
    obs, lone = Observability(), Tracer()
    frt = _boutique_fleet(4, "waterfill", _pool(), obs=obs, tracer=lone)
    frt.tick(24)
    assert frt.active_tracer() is obs.tracer
    assert lone.spans == []
    (tick,) = obs.tracer.by_name("fleet.tick")
    assert [s.name for s in _children(obs.tracer, tick)] == TICK_CHILDREN
    (plan,) = obs.tracer.by_name("fleet.plan")
    # one waterfill pass: no price fold of its own, no load update
    (rnd,) = obs.tracer.by_name("fleet.round")
    assert [s.name for s in _children(obs.tracer, rnd)] == [
        "fleet.fold", "fleet.dispatch", "fleet.wait", "fleet.fetch"]


def test_detached_runtime_records_nothing_and_commits_the_same(traced):
    frt_on, _, recs_on = traced
    off = Tracer(enabled=False)
    frt_off, recs_off = _run(tracer=off)
    assert off.spans == [] and frt_off.active_tracer() is None
    for a, b in zip(recs_on, recs_off):
        assert (a.held, a.repaired, a.refused) == (b.held, b.repaired,
                                                   b.refused)
        for name, r in a.records.items():
            q = b.records[name]
            assert (r.switched, r.migrations, r.restarts, r.emissions_g) \
                == (q.switched, q.migrations, q.restarts, q.emissions_g)
        np.testing.assert_array_equal(a.capacity.cpu_load,
                                      b.capacity.cpu_load)
    for fa in frt_on.apps:
        assert frt_on.runtime(fa.name).current \
            == frt_off.runtime(fa.name).current
