"""Observability overhead gate: the unified metrics/tracing/ledger layer
must be (nearly) free.

Three checks, all self-contained ratios (no committed baseline):

* **Eager tick overhead** — the same continuum trace with a full
  ``Observability`` bundle attached vs detached, interleaved
  best-of-rounds so host drift biases neither side.  Gate: enabled wall
  time <= ``EAGER_OVERHEAD_CEILING`` x disabled.
* **Fused-path compile hygiene** — the metrics-carrying ``lax.scan``
  variant is its own XLA program (compiled once); a warm scanned run
  with the registry attached must show ZERO planner-cache misses under
  ``metrics_scope`` and zero per-tick compiles.  The scanned decisions
  must be bit-identical with and without the registry.
* **Scanned overhead** — warm scanned run enabled vs disabled.  The
  in-scan metric accumulator is 8 extra lanes on an already-fused
  program, so the ratio must stay under ``SCAN_OVERHEAD_CEILING``
  (generous: at smoke scale the scan segment is milliseconds and noisy).
* **Watchtower (detectors armed, observe mode)** — the same two ratios
  with a ``Watchtower`` + SLO engine attached (EWMA/CUSUM detector
  lanes ride the scan carry), under the same ceilings, plus the
  ``slo_watch`` accuracy gate: on the seeded fault trace every injected
  event must raise its alert within one tick, the clean trace must stay
  silent, scanned and eager alert streams must match, and the SLO
  budget must equal the ordered sum of the billing-ledger cells
  bit-for-bit.  Full runs merge the ``slo_watch`` section into
  ``BENCH_continuum.json`` next to ``fault_recovery``.

  PYTHONPATH=src python -m benchmarks.observability_overhead [--smoke]
      [--check]
"""
import argparse
import json
import os
import time

from benchmarks.continuum_loop import (
    OUT_JSON as CONTINUUM_JSON,
    _carbon_planner,
    build_scenario,
)
from repro.continuum import (
    CarbonTrace,
    ContinuumRuntime,
    REGION_PRESETS,
    RuntimeConfig,
    WorkloadTrace,
)
from repro.core.pipeline import GreenConstraintPipeline
from repro.jax_cache import enable_persistent_cache
from repro.obs import Observability, SLO, Watchtower, metrics_scope

OUT_JSON = "BENCH_observability.json"
EAGER_OVERHEAD_CEILING = 1.05    # +5% on the eager tick loop
SCAN_OVERHEAD_CEILING = 1.20     # scan segment is tiny and noisy at smoke

# Which alert each seeded fault kind must raise (within one tick of the
# event's start).
ALERT_FOR_EVENT = {
    "node_outage": "node_down",
    "zone_blackout": "feed_stale",
    "telemetry_dropout": "telemetry_stale",
    "workload_spike": "energy_anomaly",
}


def _decisions(result):
    return [(r.replanned, r.switched, r.migrations, r.restarts,
             r.emissions_g, r.migration_g) for r in result.ticks]


def _fresh(app, infra, start, ticks, seed, obs, watch=False):
    rt = ContinuumRuntime(
        app, infra,
        CarbonTrace(REGION_PRESETS, hours=start + ticks + 25, seed=seed),
        WorkloadTrace(app, seed=seed),
        config=RuntimeConfig(scenarios=4, hysteresis_g=30.0),
        pipeline=GreenConstraintPipeline(), planner=_carbon_planner())
    if obs:
        rt.obs = Observability()
    if watch:
        rt.watch = Watchtower(slos=[
            SLO(name="run-budget", kind="carbon_budget",
                target=1e9, window_h=24)])
    return rt


def _interleaved(mk_a, mk_b, run, rounds):
    """Best-of-``rounds`` wall time for two runtime factories, alternating
    a/b per round so slow host drift (frequency scaling, background load)
    biases neither side."""
    best_a = best_b = None
    for _ in range(rounds):
        for which, mk in (("a", mk_a), ("b", mk_b)):
            rt = mk()
            t0 = time.perf_counter()
            run(rt)
            dt = time.perf_counter() - t0
            if which == "a":
                best_a = dt if best_a is None else min(best_a, dt)
            else:
                best_b = dt if best_b is None else min(best_b, dt)
    return best_a, best_b


def run(report=print, smoke=False, check=None, out_json=OUT_JSON, seed=0):
    check = (not smoke) if check is None else check
    start = 24
    ticks = 24 if smoke else 96
    rounds = 3 if smoke else 5
    app, infra = build_scenario()
    mk_off = lambda: _fresh(app, infra, start, ticks, seed, obs=False)
    mk_on = lambda: _fresh(app, infra, start, ticks, seed, obs=True)

    report(f"# Observability overhead: {ticks} ticks, "
           f"{len(app.services)} services, {len(infra.nodes)} nodes, "
           f"best of {rounds} interleaved rounds")

    # -- eager: full bundle attached vs detached ----------------------
    mk_off().run(start, 2)    # compile warmup: time the loop, not XLA
    res_off = mk_off().run(start, ticks)
    res_on_rt = mk_on()
    res_on = res_on_rt.run(start, ticks)
    assert _decisions(res_off) == _decisions(res_on), \
        "observability changed eager decisions"
    em_led, mig_led = res_on_rt.obs.ledger.totals()
    assert em_led == sum(r.emissions_g for r in res_on.ticks)
    assert mig_led == sum(r.migration_g for r in res_on.ticks)
    t_off, t_on = _interleaved(mk_off, mk_on, lambda rt: rt.run(start, ticks),
                               rounds)
    eager_ratio = t_on / max(t_off, 1e-9)
    report(f"  eager: disabled {t_off*1e3:.1f}ms | enabled {t_on*1e3:.1f}ms "
           f"-> {eager_ratio:.3f}x (ceiling {EAGER_OVERHEAD_CEILING}x)")

    # -- scanned: compile hygiene + decision parity + overhead --------
    mk_off().run_scanned(start, ticks)   # compile the plain scan variant
    mk_on().run_scanned(start, ticks)    # compile the metrics scan variant
    rt_w = mk_on()
    with metrics_scope() as scope:
        res_scan_on = rt_w.run_scanned(start, ticks)
    assert rt_w.last_scanned_fallback is None, rt_w.last_scanned_fallback
    warm_misses = int(scope.delta("planner.compile.misses"))
    warm_compiles = int(sum(r.compiles for r in res_scan_on.ticks))
    assert warm_misses == 0, (
        f"metrics scan recompiled in steady state: {warm_misses} misses")
    assert warm_compiles == 0, warm_compiles
    res_scan_off = mk_off().run_scanned(start, ticks)
    assert _decisions(res_scan_off) == _decisions(res_scan_on) \
        == _decisions(res_off), "observability changed scanned decisions"
    t_s_off, t_s_on = _interleaved(
        mk_off, mk_on, lambda rt: rt.run_scanned(start, ticks), rounds)
    scan_ratio = t_s_on / max(t_s_off, 1e-9)
    report(f"  scanned: disabled {t_s_off*1e3:.1f}ms | enabled "
           f"{t_s_on*1e3:.1f}ms -> {scan_ratio:.3f}x "
           f"(ceiling {SCAN_OVERHEAD_CEILING}x); warm recompiles 0")

    # -- watchtower: detectors armed in observe mode ------------------
    mk_watch = lambda: _fresh(app, infra, start, ticks, seed, obs=False,
                              watch=True)
    rt_watch = mk_watch()
    res_watch = rt_watch.run(start, ticks)
    assert _decisions(res_watch) == _decisions(res_off), \
        "observe-mode watchtower changed eager decisions"
    # budget bitwise: the SLO budget is the ordered plain sum of the
    # per-tick accounted emissions — the same cells a billing ledger
    # records and billing_report sums.
    b = 0.0
    for r in res_watch.ticks:
        b = b + (r.emissions_g + r.migration_g)
    budget_bitwise = rt_watch.watch.budget_spent_g == b
    assert budget_bitwise, (rt_watch.watch.budget_spent_g, b)
    t_woff, t_won = _interleaved(mk_off, mk_watch,
                                 lambda rt: rt.run(start, ticks), rounds)
    watch_eager_ratio = t_won / max(t_woff, 1e-9)
    report(f"  watch eager: detached {t_woff*1e3:.1f}ms | armed "
           f"{t_won*1e3:.1f}ms -> {watch_eager_ratio:.3f}x "
           f"(ceiling {EAGER_OVERHEAD_CEILING}x)")
    mk_watch().run_scanned(start, ticks)     # compile the watch variant
    rt_ws = mk_watch()
    res_watch_scan = rt_ws.run_scanned(start, ticks)
    assert rt_ws.last_scanned_fallback is None, rt_ws.last_scanned_fallback
    assert _decisions(res_watch_scan) == _decisions(res_off), \
        "observe-mode watchtower changed scanned decisions"
    assert rt_ws.watch.budget_spent_g == rt_watch.watch.budget_spent_g
    t_ws_off, t_ws_on = _interleaved(
        mk_off, mk_watch, lambda rt: rt.run_scanned(start, ticks), rounds)
    watch_scan_ratio = t_ws_on / max(t_ws_off, 1e-9)
    report(f"  watch scanned: detached {t_ws_off*1e3:.1f}ms | armed "
           f"{t_ws_on*1e3:.1f}ms -> {watch_scan_ratio:.3f}x "
           f"(ceiling {SCAN_OVERHEAD_CEILING}x)")

    acc = _alert_accuracy(report, smoke)

    out = {"ticks": ticks, "rounds": rounds,
           "eager": {"t_disabled_s": t_off, "t_enabled_s": t_on,
                     "ratio": eager_ratio,
                     "ceiling": EAGER_OVERHEAD_CEILING},
           "scanned": {"t_disabled_s": t_s_off, "t_enabled_s": t_s_on,
                       "ratio": scan_ratio,
                       "ceiling": SCAN_OVERHEAD_CEILING,
                       "warm_compile_misses": warm_misses},
           "slo_watch": {
               "eager_ratio": watch_eager_ratio,
               "eager_ceiling": EAGER_OVERHEAD_CEILING,
               "scanned_ratio": watch_scan_ratio,
               "scanned_ceiling": SCAN_OVERHEAD_CEILING,
               "budget_bitwise": budget_bitwise,
               **acc,
           }}
    if check:
        assert eager_ratio <= EAGER_OVERHEAD_CEILING, (t_on, t_off)
        assert scan_ratio <= SCAN_OVERHEAD_CEILING, (t_s_on, t_s_off)
        assert watch_eager_ratio <= EAGER_OVERHEAD_CEILING, (t_won, t_woff)
        assert watch_scan_ratio <= SCAN_OVERHEAD_CEILING, (t_ws_on, t_ws_off)
        assert acc["matched_events"] == acc["events"], acc
        assert acc["max_lag_ticks"] <= 1, acc
        assert acc["clean_false_positives"] == 0, acc
        assert acc["alert_parity_scanned"], acc
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(out, fh, indent=2, sort_keys=True)
        report(f"# wrote {out_json}")
        # Full runs park the accuracy section next to fault_recovery's
        # in the continuum BENCH blob (merge, don't overwrite).
        blob = {}
        if os.path.exists(CONTINUUM_JSON):
            with open(CONTINUUM_JSON) as fh:
                blob = json.load(fh)
        blob["slo_watch"] = out["slo_watch"]
        with open(CONTINUUM_JSON, "w") as fh:
            json.dump(blob, fh, indent=2)
        report(f"# merged 'slo_watch' into {CONTINUUM_JSON}")
    return out


def _alert_accuracy(report, smoke):
    """Alert accuracy on the seeded fault trace: every injected event
    must raise its mapped alert within one tick of the event start, the
    clean twin of the trace must raise nothing, and the scanned path
    must reproduce the eager alert stream exactly."""
    from benchmarks.fault_recovery import REGIONS, fault_events, make_runtime
    from repro.faults import FaultTrace

    start = 24
    ticks = 48 if smoke else 168
    app, infra = build_scenario(n_services=8, regions=REGIONS)
    events = fault_events(start, ticks)

    def mk(faulty):
        kw = dict(scenarios=4, hysteresis_g=30.0)
        if faulty:
            node_ids = tuple(n.node_id for n in infra.nodes)
            kw["faults"] = FaultTrace.from_events(
                node_ids, REGIONS, start + ticks, events)
        rt = make_runtime(
            app, infra,
            CarbonTrace(REGION_PRESETS, hours=start + ticks + 25, seed=7),
            WorkloadTrace(app, seed=11), RuntimeConfig(**kw))
        rt.watch = Watchtower()
        return rt

    rt_clean = mk(False)
    rt_clean.run(start, ticks)
    clean_fp = len(rt_clean.watch.alerts)

    rt_faulty = mk(True)
    rt_faulty.run(start, ticks)
    alerts = [(a.t, a.name, a.target) for a in rt_faulty.watch.alerts]

    matched, max_lag = 0, 0
    for ev in events:
        name = ALERT_FOR_EVENT[ev.kind]
        target = ev.target if ev.kind in ("node_outage",
                                          "zone_blackout") else None
        lags = [abs(t - ev.start) for t, n, tgt in alerts
                if n == name and abs(t - ev.start) <= 1
                and (target is None or tgt == target)]
        if lags:
            matched += 1
            max_lag = max(max_lag, min(lags))

    rt_scan = mk(True)
    rt_scan.run_scanned(start, ticks)
    parity = (rt_scan.last_scanned_fallback is None
              and [(a.t, a.name, a.target)
                   for a in rt_scan.watch.alerts] == alerts)

    report(f"  slo_watch accuracy ({ticks} ticks): "
           f"{matched}/{len(events)} events alerted (max lag {max_lag}), "
           f"{clean_fp} clean false positives, scanned parity {parity}")
    return {"accuracy_ticks": ticks, "events": len(events),
            "matched_events": matched, "max_lag_ticks": max_lag,
            "clean_false_positives": clean_fp,
            "alert_parity_scanned": parity,
            "n_alerts": len(alerts)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trace, fewer rounds")
    ap.add_argument("--check", action="store_true",
                    help="enforce the overhead ceilings even under --smoke")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args()
    enable_persistent_cache()
    run(smoke=args.smoke, check=args.check or None,
        out_json=None if (args.no_json or args.smoke) else OUT_JSON)


if __name__ == "__main__":
    main()
