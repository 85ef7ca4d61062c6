"""The fleet tick enriches the shared machines once for each group of
tenants that would compute the same enrichment (one carbon view, one set
of gatherer settings), and hands the result to every tenant's pipeline.

* **same answers** — constraints, enriched machines (carbon and
  forecasts), lowerings (``E``, ``ci``), candidates, commits and
  accounted emissions are bit-identical to a fleet whose tenants each
  enrich their own, on a contended price fleet;
* **counters** — ``fleet.ingest`` reads ``enrichments`` 1 and
  ``enrich_shared`` tenants − 1 on every tick without faults;
* **other settings** — a tenant whose gatherer has another window or
  fallback forecast gets an enrichment of its own, with its own values;
* **faults** — each tenant plans on a degraded view of its own, so each
  enriches its own, and every tick matches the per-tenant path.
"""
import pytest

from repro.configs.boutique import EUROPE_CI, build_application
from repro.continuum import RuntimeConfig, WorkloadTrace
from repro.continuum.whatif import plan_assignment
from repro.faults import FaultEvent, FaultTrace
from repro.fleet import FleetApp, FleetRuntime
from repro.obs import Tracer

from test_fleet_price_commit import _carbon, _pool

TICKS = range(24, 28)
TENANTS = 4


def _fleet(n, faults=None, tracer=None):
    """``n`` Boutique tenants, price-coupled on the contended pool."""
    app = build_application()
    apps = [FleetApp(f"t{i:02d}", app,
                     WorkloadTrace(app, seed=i, peak_hour=float(i % 24)))
            for i in range(n)]
    return FleetRuntime(apps, _pool(), _carbon(),
                        config=RuntimeConfig(horizon_h=6, hysteresis_g=30.0,
                                             faults=faults),
                        coupling="price", max_batch=8, tracer=tracer)


def _own_enrichment(frt):
    """Every tenant's pipeline enriches the machines itself: the
    enrichment the fleet tick hands it is dropped."""
    for fa in frt.apps:
        pipe = frt.runtime(fa.name).pipeline
        pipe.run = lambda *a, _run=pipe.run, enriched=None, **kw: \
            _run(*a, **kw)


def _record_outputs(frt, log):
    """Append each tenant's pipeline output to ``log`` as it is made."""
    for fa in frt.apps:
        pipe = frt.runtime(fa.name).pipeline

        def run(*a, _run=pipe.run, **kw):
            out = _run(*a, **kw)
            log.append(out)
            return out

        pipe.run = run


def _drive(frt, ticks=TICKS):
    """Each tick's answers, in tenant order, exact to the bit: the
    pipelines' outputs (reprs, in which a float reads as its exact value
    and a dropout's NaN reads equal to itself), the cached lowerings'
    ``E`` and ``ci`` bytes, candidates, commits and accounted records."""
    log = []
    _record_outputs(frt, log)
    answers = []
    for t in ticks:
        del log[:]
        frec = frt.tick(t)
        runtimes = [frt.runtime(fa.name) for fa in frt.apps]
        lows = [rt.pipeline._lowering_cache[2] for rt in runtimes]
        answers.append({
            "outputs": [repr((o.constraints, o.infra, o.computation,
                              o.communication)) for o in log],
            "forecasts": [[n.carbon_forecast for n in o.infra.nodes]
                          for o in log],
            "E": [low.E.tobytes() for low in lows],
            "ci": [low.ci.tobytes() for low in lows],
            "candidates": [plan_assignment(r.plans[0])
                           if r.plans[0].feasible else None
                           for r in frt.last_result.results],
            "committed": [dict(rt.current or {}) for rt in runtimes],
            "records": [repr((r.emissions_g, r.migration_g, r.migrations,
                              r.restarts, r.switched, r.expected_saving_g,
                              r.n_constraints, r.emergency, r.violations))
                        for r in frec.records.values()],
            "evicted": [r.evicted for r in frec.records.values()],
            "commit": (frec.held, frec.repaired, frec.refused),
        })
    return answers, log


def _pair(faults=None, tracer=None):
    """The same fleet twice: one enriching once per group and traced by
    ``tracer``, one enriching per tenant."""
    shared = _fleet(TENANTS, faults, tracer)
    own = _fleet(TENANTS, faults)
    _own_enrichment(own)
    return shared, own


def _ingest_counters(tr):
    return [(s.attrs["enrichments"], s.attrs["enrich_shared"])
            for s in tr.by_name("fleet.ingest")]


@pytest.fixture(scope="module")
def shared_and_own():
    tr = Tracer()
    shared, own = _pair(tracer=tr)
    return _drive(shared), _drive(own)[0], tr


def test_shared_enrichment_is_bit_identical(shared_and_own):
    (shared, last), own, _ = shared_and_own
    assert len(shared) == len(own) == len(TICKS)
    for a, b in zip(shared, own):
        assert a == b
    # every tenant was handed the one enrichment
    assert all(o.infra is last[0].infra for o in last)
    assert all(n.carbon is not None and n.carbon_forecast
               for n in last[0].infra.nodes)
    # every tenant rolled out on the first tick
    assert all(shared[0]["committed"])


@pytest.mark.parametrize("tenants", [1, TENANTS])
def test_ingest_counts_one_enrichment_a_tick(tenants, shared_and_own):
    if tenants == TENANTS:
        tr = shared_and_own[2]
    else:
        tr = Tracer()
        frt = _fleet(tenants, tracer=tr)
        for t in TICKS:
            frt.tick(t)
    assert _ingest_counters(tr) == [(1, tenants - 1)] * len(TICKS)


# The fleet plans every tenant on one ci, so the settings differ in ways
# that leave it alone: windows that both reach back past the trace's
# first hour, and a fallback forecast that the fleet's forecast overrides.
OTHER_SETTINGS = {
    "window": (dict(window=48), dict(window=96)),
    "forecast_from_history": ({}, dict(forecast_from_history=False)),
}


@pytest.mark.parametrize("setting", sorted(OTHER_SETTINGS))
def test_other_gatherer_settings_get_their_own_enrichment(setting):
    everyone, t01 = OTHER_SETTINGS[setting]
    tr = Tracer()
    shared, own = _pair(tracer=tr)
    for frt in (shared, own):
        for fa in frt.apps:
            gatherer = frt.runtime(fa.name).pipeline.gatherer
            for k, v in {**everyone, **(t01 if fa.name == "t01"
                                        else {})}.items():
                setattr(gatherer, k, v)
    (a, last), (b, _) = _drive(shared), _drive(own)
    assert a == b
    assert _ingest_counters(tr) == [(2, TENANTS - 2)] * len(TICKS)
    others = [o for i, o in enumerate(last) if i != 1]
    assert all(o.infra is others[0].infra for o in others)
    assert last[1].infra is not others[0].infra


def _fault_trace(infra, ticks):
    ids = [n.node_id for n in infra.nodes]
    return FaultTrace.from_events(ids, sorted(EUROPE_CI), ticks, [
        FaultEvent("node_outage", "france-0", 25, 2),
        FaultEvent("zone_blackout", "france", 24, 3),
        FaultEvent("zone_blackout", "spain", 26, 2),
        FaultEvent("telemetry_dropout", "", 26, 1),
        FaultEvent("workload_spike", "", 27, 1, 2.0),
    ])


def test_fault_fleet_matches_per_tenant_enrichment():
    tr = Tracer()
    ticks = range(24, 30)
    shared, own = _pair(_fault_trace(_pool(), 40), tracer=tr)
    (a, _), (b, _) = _drive(shared, ticks), _drive(own, ticks)
    for x, y in zip(a, b):
        assert x == y
    # each tenant's degraded view is its own: one enrichment each
    assert _ingest_counters(tr) == [(TENANTS, 0)] * len(ticks)
    # the faults reached the plans: evictions, and a dropout's NaN
    assert any(sum(x["evicted"]) for x in a)
    assert any("nan" in o for x in a for o in x["outputs"])
