"""Unified observability layer: metrics registry + scope deltas, span
tracer, per-service emissions ledger, exporters, and the hard parity
contracts — the ledger must sum bit-equal to the TickRecord totals on
the eager, scanned, and drift-fallback paths, and a disabled registry
must add ZERO arrays to the fused scan carry."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.continuum import ContinuumResult, FallbackEvent
from repro.continuum import megaloop
from repro.obs import (
    EmissionsLedger,
    MetricsRegistry,
    Observability,
    Span,
    Tracer,
    events_from_jsonl,
    events_jsonl,
    metrics_scope,
    prometheus_text,
)

from test_megaloop import START, _DriftingWorkload, _runtime, _scenario

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "prometheus_golden.txt")


def _obs_runtime(app, infra, ticks, **kw):
    rt = _runtime(app, infra, ticks, **kw)
    rt.obs = Observability()
    return rt


def _decisions(result):
    # the repo's eager-vs-scanned parity contract: decisions, emissions,
    # and charges bit-equal (expected_saving_g is only allclose across
    # the XLA/numpy mean reduction, same as tests/test_megaloop.py)
    return [(r.replanned, r.switched, r.migrations, r.restarts,
             r.emissions_g, r.migration_g) for r in result.ticks]


def _assert_ledger_parity(obs, result):
    """The per-(service, flavour, node, zone) ledger cells must decompose
    the TickRecord totals exactly — per tick AND in aggregate."""
    entries = obs.ledger.entries
    assert len(entries) == len(result.ticks)
    for e, r in zip(entries, result.ticks):
        assert e.t == r.t
        assert e.emissions_g == r.emissions_g          # bit-equal
        assert e.migration_g == r.migration_g          # bit-equal
    em, mig = obs.ledger.totals()
    assert em == sum(r.emissions_g for r in result.ticks)
    assert mig == sum(r.migration_g for r in result.ticks)
    # attribution views decompose the same total (float re-association
    # across dict groupings: close, not bit-equal)
    total = em + mig
    for view in (obs.ledger.by_service(), obs.ledger.by_node(),
                 obs.ledger.by_zone()):
        np.testing.assert_allclose(sum(view.values()), total, rtol=1e-12)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 2.5)
    reg.inc("a.count", labels={"path": "full"})
    reg.gauge("a.level", 3.0)
    reg.gauge("a.level", 7.0)
    for v in (0.002, 0.004, 40.0):
        reg.observe("a.lat", v)
    assert reg.value("a.count") == 3.5
    assert reg.value("a.count", labels={"path": "full"}) == 1.0
    assert reg.value("a.level") == 7.0
    h = reg.histogram("a.lat")
    assert (h.count, h.min, h.max) == (3, 0.002, 40.0)
    assert h.sum == pytest.approx(40.006)


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.inc("x")
    reg.gauge("y", 1.0)
    reg.observe("z", 1.0)
    reg.event("e", tick=3)
    assert reg.value("x") == 0.0
    assert not reg.counters() and not reg.gauges()
    assert not reg.histograms() and not reg.events


def test_metrics_scope_reads_deltas_without_reset():
    reg = MetricsRegistry()
    reg.inc("c", 10.0)
    with metrics_scope(reg) as scope:
        reg.inc("c", 4.0)
        with metrics_scope(reg) as inner:   # overlapping scopes
            reg.inc("c", 1.0)
        assert inner.delta("c") == 1.0
    assert scope.delta("c") == 5.0
    # nothing was reset: globals keep their absolute value and the scope
    # stays frozen after exit
    assert reg.value("c") == 15.0
    reg.inc("c", 100.0)
    assert scope.delta("c") == 5.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _golden_registry() -> MetricsRegistry:
    """Deterministic registry for the exposition golden (no wall times)."""
    reg = MetricsRegistry()
    reg.describe("planner.compile.hits", "counter",
                 help="planner cache hits")
    reg.inc("planner.compile.hits", 7)
    reg.inc("planner.compile.misses", 2)
    reg.inc("lowering.path", 3, labels={"path": "delta"})
    reg.inc("lowering.path", 1, labels={"path": "full"})
    # Label values that need exposition-format escaping.
    reg.inc("watch.alerts", 1,
            labels={"name": 'zone "wind\\north"\nline2'})
    reg.gauge("engine.candidates", 120)
    reg.describe("stage.plan_s", "histogram", help="plan stage seconds",
                 buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.02, 0.02, 0.5):
        reg.observe("stage.plan_s", v)
    return reg


def test_prometheus_exposition_matches_golden():
    text = prometheus_text(_golden_registry())
    with open(GOLDEN) as fh:
        assert text == fh.read()


def test_prometheus_cumulative_buckets():
    text = prometheus_text(_golden_registry())
    assert 'repro_stage_plan_s_bucket{le="0.01"} 1' in text
    assert 'repro_stage_plan_s_bucket{le="0.1"} 3' in text
    assert 'repro_stage_plan_s_bucket{le="+Inf"} 4' in text
    assert "repro_stage_plan_s_count 4" in text
    assert 'repro_lowering_path_total{path="delta"} 3' in text


def test_event_jsonl_round_trip():
    reg = MetricsRegistry()
    reg.event("runtime.scanned_fallback", tick=31,
              reason="engine structural key drifted mid-trace",
              detail="abc -> def")
    reg.event("custom", value=1.5)
    back = events_from_jsonl(events_jsonl(reg))
    assert back == reg.events


def test_span_tracer_nesting_and_round_trip():
    tr = Tracer()
    with tr.span("tick", t=3):
        with tr.span("constraints"):
            pass
        with tr.span("plan"):
            pass
    [tick] = tr.by_name("tick")
    kids = tr.children(tick.span_id)
    assert [s.name for s in kids] == ["constraints", "plan"]
    assert all(s.parent == tick.span_id for s in kids)
    assert tick.attrs == {"t": 3}
    assert tick.duration_s >= 0.0
    assert Tracer.from_jsonl(tr.to_jsonl()) == tr.spans


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("tick"):
        pass
    assert tr.add("x", 0.0, 1.0) == -1
    assert tr.spans == []


# ---------------------------------------------------------------------------
# eager path: parity, spans, fallback events
# ---------------------------------------------------------------------------


def test_eager_ledger_bit_parity_and_spans():
    app, infra = _scenario(n_services=8)
    rt = _obs_runtime(app, infra, 10)
    res = rt.run(START, 10)
    _assert_ledger_parity(rt.obs, res)
    reg = rt.obs.registry
    assert reg.value("runtime.ticks") == 10.0
    assert reg.value("runtime.replans") == \
        sum(r.replanned for r in res.ticks)
    assert reg.value("runtime.migrations") == \
        sum(r.migrations for r in res.ticks)
    ticks = rt.obs.tracer.by_name("tick")
    assert len(ticks) == 10
    kids = {s.name for s in rt.obs.tracer.children(ticks[0].span_id)}
    assert {"telemetry.ingest", "constraints", "plan.evaluate",
            "switch", "account"} <= kids


def test_eager_decisions_identical_with_and_without_obs():
    app, infra = _scenario(n_services=8)
    res_plain = _runtime(app, infra, 10).run(START, 10)
    res_obs = _obs_runtime(app, infra, 10).run(START, 10)
    assert _decisions(res_plain) == _decisions(res_obs)


# ---------------------------------------------------------------------------
# scanned path: parity, carry hygiene, fallback events
# ---------------------------------------------------------------------------


def test_scanned_ledger_bit_parity_matches_eager():
    app, infra = _scenario(n_services=8)
    rt_e = _obs_runtime(app, infra, 12)
    rt_s = _obs_runtime(app, infra, 12)
    res_e = rt_e.run(START, 12)
    res_s = rt_s.run_scanned(START, 12)
    assert rt_s.last_scanned_fallback is None
    assert _decisions(res_e) == _decisions(res_s)
    _assert_ledger_parity(rt_s.obs, res_s)
    # the in-scan accumulator agrees with the committed records
    reg = rt_s.obs.registry
    assert reg.value("scan.cum.emissions_g") == pytest.approx(
        sum(r.emissions_g for r in res_s.ticks))
    assert reg.value("runtime.migrations") == \
        sum(r.migrations for r in res_s.ticks)
    tr = rt_s.obs.tracer
    (root,) = tr.by_name("run_scanned")
    names = [root.name] + [s.name for s in tr.children(root.span_id)]
    assert names == ["run_scanned", "scan.stage", "scan.fused",
                     "scan.commit"]


def test_scanned_disabled_obs_adds_zero_carry_arrays(monkeypatch):
    """Without a registry the fused program must carry exactly the four
    decision arrays and 14 ys (12 decision/accounting columns plus the
    fault-eviction pair) — observability must cost the scanned path
    literally nothing when off."""
    seen = {}
    orig = megaloop._commit

    def spy(runtime, st, carry_out, ys, *a, **kw):
        seen["carry"] = len(carry_out)
        seen["ys"] = len(ys)
        return orig(runtime, st, carry_out, ys, *a, **kw)

    monkeypatch.setattr(megaloop, "_commit", spy)
    app, infra = _scenario(n_services=8)
    rt_off = _runtime(app, infra, 8)
    rt_off.run_scanned(START, 8)
    assert (seen["carry"], seen["ys"]) == (4, 14)
    rt_on = _obs_runtime(app, infra, 8)
    rt_on.run_scanned(START, 8)
    assert (seen["carry"], seen["ys"]) == (5, 15)
    # a watchtower appends ONE nested detector-state lane (and one
    # stacked watch row) to the fused program, with or without the
    # metrics accumulator — but commit still sees the core tuples only
    # (the watch lanes are split off for watch.commit_scan)
    from repro.obs import Watchtower
    fused = {}
    orig_fn = megaloop._scan_fn

    def spy_fn(kind, with_metrics=False, with_watch=False):
        fn = orig_fn(kind, with_metrics=with_metrics, with_watch=with_watch)

        def wrapped(carry0, xs, consts, wconsts):
            carry_out, ys = fn(carry0, xs, consts, wconsts)
            fused["carry"] = len(carry_out)
            fused["ys"] = len(ys)
            return carry_out, ys
        return wrapped

    monkeypatch.setattr(megaloop, "_scan_fn", spy_fn)
    rt_w = _runtime(app, infra, 8)
    rt_w.watch = Watchtower()
    rt_w.run_scanned(START, 8)
    assert rt_w.last_scanned_fallback is None
    assert (fused["carry"], fused["ys"]) == (5, 15)
    assert (seen["carry"], seen["ys"]) == (4, 14)
    rt_both = _obs_runtime(app, infra, 8)
    rt_both.watch = Watchtower()
    rt_both.run_scanned(START, 8)
    assert (fused["carry"], fused["ys"]) == (6, 16)
    assert (seen["carry"], seen["ys"]) == (5, 15)


def test_drift_fallback_records_event_and_keeps_parity():
    app, infra = _scenario()
    rt_e = _obs_runtime(app, infra, 8)
    rt_s = _obs_runtime(app, infra, 8)
    rt_e.workload = _DriftingWorkload(rt_e.workload, START + 3)
    rt_s.workload = _DriftingWorkload(rt_s.workload, START + 3)
    res_e = rt_e.run(START, 8)
    res_s = rt_s.run_scanned(START, 8)
    # old attribute still the most-recent view...
    assert rt_s.last_scanned_fallback == \
        "engine structural key drifted mid-trace"
    # ...and the structured list carries tick + detail
    [ev] = rt_s.scanned_fallbacks
    assert isinstance(ev, FallbackEvent)
    assert ev.reason == rt_s.last_scanned_fallback
    assert ev.tick == START + 3
    assert "->" in ev.detail
    [rev] = [e for e in rt_s.obs.registry.events
             if e["name"] == "runtime.scanned_fallback"]
    assert rev["tick"] == ev.tick and rev["reason"] == ev.reason
    # the eager replay under the fallback still feeds the ledger
    assert _decisions(res_e) == _decisions(res_s)
    _assert_ledger_parity(rt_s.obs, res_s)


# ---------------------------------------------------------------------------
# result serialization + report
# ---------------------------------------------------------------------------


def test_continuum_result_jsonl_round_trip(tmp_path):
    app, infra = _scenario(n_services=8)
    res = _runtime(app, infra, 6).run(START, 6)
    back = ContinuumResult.from_jsonl(res.to_jsonl())
    assert back == res                      # bit-exact float round trip
    p = tmp_path / "trace.jsonl"
    res.to_jsonl(str(p))
    assert ContinuumResult.from_jsonl(str(p)) == res
    header = json.loads(p.read_text().splitlines()[0])
    assert header["schema"] == "continuum-result/v1"
    with pytest.raises(ValueError):
        ContinuumResult.from_jsonl('{"schema": "bogus"}')


def test_run_report_renders_all_sections():
    app, infra = _scenario(n_services=8)
    rt = _obs_runtime(app, infra, 8)
    res = rt.run(START, 8)
    txt = rt.obs.report(res)
    assert "Green audit: 8 ticks" in txt
    assert "attribution (ledger)" in txt
    assert "stage latency" in txt
    assert "svc0" in txt
    # and the bare-result report (no obs handles) still works
    assert "Green audit" in res.render_report()


def test_ledger_cells_decompose_entries():
    app, infra = _scenario(n_services=8)
    rt = _obs_runtime(app, infra, 10)
    res = rt.run(START, 10)
    for e, r in zip(rt.obs.ledger.entries, res.ticks):
        cells = list(e.cells())
        total = sum(g for *_k, g in cells)
        np.testing.assert_allclose(
            total, r.emissions_g + r.migration_g, rtol=1e-12, atol=1e-9)
        kinds = {kind for _s, _f, _n, _z, kind, _g in cells}
        assert kinds <= {"comp", "comm", "migration"}


# ---------------------------------------------------------------------------
# Exposition hardening: label/HELP escaping
# ---------------------------------------------------------------------------


def test_prometheus_label_and_help_escaping():
    from repro.obs.export import _escape_help, _escape_label
    assert _escape_label('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    # backslash escaped first: an already-escaped-looking value doubles
    assert _escape_label("\\n") == "\\\\n"
    assert _escape_help("line1\nline2 \\x") == "line1\\nline2 \\\\x"
    reg = MetricsRegistry()
    reg.describe("weird", "counter", help="multi\nline help")
    reg.inc("weird", 2, labels={"zone": 'wind "north"\nplus\\more'})
    text = prometheus_text(reg)
    assert '# HELP repro_weird_total multi\\nline help' in text
    assert 'zone="wind \\"north\\"\\nplus\\\\more"' in text
    # every emitted line is a single exposition line (no raw newlines
    # smuggled through label values or help text)
    assert all(ln.startswith(("#", "repro_")) for ln in text.splitlines())


# ---------------------------------------------------------------------------
# ContinuumResult JSONL round-trip under faults (fallbacks + emergency
# migrations in the ledger)
# ---------------------------------------------------------------------------


def test_jsonl_round_trip_carries_fault_events_and_emergency_ledger():
    """A faulty scanned run that (a) takes the structured capacity-derate
    fallback and (b) emergency-migrates stranded services must round-trip
    through to_jsonl/from_jsonl bit-exactly, with the eviction fields and
    the emergency migration charges intact."""
    from repro.continuum.loop import FallbackReason
    from repro.faults import FaultEvent, FaultTrace

    app, infra = _scenario(n_services=6)
    ticks = 16
    node_ids = [n.node_id for n in infra.nodes]
    regions = ("solar-south", "wind-north", "coal-east")
    ft = FaultTrace.from_events(node_ids, regions, START + ticks, [
        FaultEvent("node_outage", "wind-north-0", START + 6, 4),
        FaultEvent("capacity_derate", "wind-north-1", START + 8, 3, 0.5),
    ])
    rt = _obs_runtime(app, infra, ticks, faults=ft)
    res = rt.run_scanned(START, ticks)

    # the run really exercised both machineries
    [ev] = rt.scanned_fallbacks
    assert isinstance(ev, FallbackEvent)
    assert ev.reason is FallbackReason.FAULT_CAPACITY_DERATE
    assert any(r.evicted > 0 for r in res.ticks)
    assert any(r.emergency for r in res.ticks)
    emergency_ticks = {r.t for r in res.ticks if r.emergency}
    mig_entries = [e for e in rt.obs.ledger.entries
                   if e.t in emergency_ticks and e.moved > 0]
    assert mig_entries, "emergency migrations must be billed in the ledger"
    for e in mig_entries:
        assert any(kind == "migration" for *_k, kind, _g in e.cells())

    back = ContinuumResult.from_jsonl(res.to_jsonl())
    assert back.final_assignment == res.final_assignment
    assert len(back.ticks) == len(res.ticks)
    for orig, rt_rec in zip(res.ticks, back.ticks):
        assert dataclasses.asdict(orig) == dataclasses.asdict(rt_rec)
    # eviction/emergency telemetry survived the trip
    assert [r.evicted for r in back.ticks] == [r.evicted for r in res.ticks]
    assert any(r.emergency for r in back.ticks)


# ---------------------------------------------------------------------------
# Launch-layer tracing: dryrun + roofline spans
# ---------------------------------------------------------------------------


def test_roofline_run_emits_spans_and_dryrun_takes_a_tracer(tmp_path):
    import inspect

    import benchmarks.roofline as roofline
    from repro.launch.dryrun import run_cell

    # one planner + launch-layer timeline: dryrun.run_cell accepts the
    # same Tracer roofline.run does (compiling a cell is too heavy for
    # unit tests, so the dryrun side is a signature/span-name contract)
    assert "tracer" in inspect.signature(run_cell).parameters

    path = tmp_path / "dryrun.jsonl"
    path.write_text(json.dumps({
        "arch": "a", "shape": "s", "multi_pod": False, "status": "skipped",
        "reason": "x"}) + "\n")
    tr = Tracer()
    out = roofline.run(report=lambda *_: None, path=str(path), tracer=tr)
    assert out["cells"] == 0 and out["skipped"] == 1
    [table] = tr.by_name("roofline.table")
    [load] = tr.by_name("roofline.load")
    assert load.parent == table.span_id
    assert load.attrs["path"] == str(path)
    # a disabled tracer records nothing (the default no-tracer path)
    assert roofline.run(report=lambda *_: None, path=str(path))["skipped"] == 1
