"""Array constraint engine vs the reference trio: bit-parity everywhere.

The ConstraintEngine (repro.learn) must produce the exact constraints of
ConstraintGenerator + KBEnricher + ConstraintRanker — same ids, impacts,
Eq. 11/12 weights, savings ranges, explanation text, and ordering — on
every path: across mu-decay ticks, empty monitoring, single-service
problems, tau edge cases (alpha = 0 / 1), both flavour/tau scopes, and
with extension modules delegated to their reference implementation.  The
incremental dirty-mask pass must match the full pass tick-for-tick.
"""
import numpy as np
import pytest

from repro.configs import boutique
from repro.continuum import CarbonTrace, REGION_PRESETS, WorkloadTrace
from repro.core.kb import KBEnricher, KnowledgeBase
from repro.core.library import ConstraintLibrary
from repro.core.pipeline import GreenConstraintPipeline
from repro.core.types import (
    Application,
    CommunicationLink,
    EnergySample,
    Flavour,
    FlavourRequirements,
    Infrastructure,
    MonitoringData,
    Node,
    NodeCapabilities,
    Service,
    TrafficSample,
)
from repro.learn import (
    ArrayKB,
    ConstraintEngine,
    TelemetryBuffer,
    quantile_inf_tensor,
)


def _pipes(**kw):
    return (GreenConstraintPipeline(engine="array", **kw),
            GreenConstraintPipeline(engine="reference", **kw))


def _app(n=5, flavours=("large", "small"), links=True):
    services = tuple(
        Service(f"svc{i}", flavours=tuple(
            Flavour(f, FlavourRequirements(cpu=1.0 + k))
            for k, f in enumerate(flavours)))
        for i in range(n))
    ls = tuple(CommunicationLink(f"svc{i}", f"svc{(i + 1) % n}")
               for i in range(n)) if links and n > 1 else ()
    return Application("t", services, ls)


def _infra(regions=("solar-south", "wind-north", "coal-east"), per=2):
    nodes = tuple(
        Node(f"{r}-{k}", region=r,
             capabilities=NodeCapabilities(cpu=16.0))
        for r in regions for k in range(per))
    return Infrastructure("t", nodes)


def _drive(pipe, app, infra, trace, workload, ticks, start=24):
    outs = []
    for t in range(start, start + ticks):
        pipe.gatherer.signal = trace.history_signal(t)
        outs.append(pipe.run(app, infra, workload.monitoring(t)))
    return outs


# ---------------------------------------------------------------------------
# single-tick parity on the paper scenarios
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", [1, 3, 4])
def test_boutique_scenarios_bit_match(scenario):
    app, infra, mon = boutique.scenario(scenario)
    pa, pr = _pipes()
    assert pa.run(app, infra, mon).constraints == \
        pr.run(app, infra, mon).constraints
    # second tick exercises KB refresh + the engine's dirty path
    assert pa.run(app, infra, mon).constraints == \
        pr.run(app, infra, mon).constraints


def test_parity_engine_asserts_and_matches():
    app, infra, mon = boutique.scenario(1)
    pipe = GreenConstraintPipeline(engine="parity")
    out = pipe.run(app, infra, mon)
    assert out.constraints
    pipe.run(app, infra, mon)


def test_parity_enabled_mid_stream_with_decaying_ck():
    """Regression: flipping to engine='parity' after array ticks must
    snapshot the shadow KB BEFORE the engine's pass — otherwise the
    reference side decays the tick's mu twice and the parity assertion
    fires spuriously on a correct engine."""
    app, infra = _app(6), _infra()
    tr = CarbonTrace(REGION_PRESETS, hours=60, seed=5)
    wl = WorkloadTrace(app, seed=5)
    pipe = GreenConstraintPipeline(engine="array", alpha=0.6)
    _drive(pipe, app, infra, tr, wl, ticks=4)
    assert any(sc.mu < 1.0 for sc in
               (pipe.kb.ck[k] for k in pipe.kb.ck)) or len(pipe.kb.ck)
    pipe.engine = "parity"
    _drive(pipe, app, infra, tr, wl, ticks=4, start=28)  # must not raise


def test_unknown_engine_rejected():
    app, infra, mon = boutique.scenario(1)
    with pytest.raises(ValueError):
        GreenConstraintPipeline(engine="nope").run(app, infra, mon)


@pytest.mark.parametrize("window", [1, 3])
def test_pre_enriched_infra_matches_own_enrichment(window, monkeypatch):
    """``run(..., enriched=...)`` handed the infrastructure its gatherer
    would build gives what ``run`` gives when it enriches, tick for tick
    through the telemetry window, and does not call the gatherer."""
    app, infra = _app(6), _infra()
    tr = CarbonTrace(REGION_PRESETS, hours=60, seed=5)
    wl = WorkloadTrace(app, seed=5)
    own, handed = (GreenConstraintPipeline(telemetry_window=window)
                   for _ in range(2))
    for t in range(24, 30):
        for pipe in (own, handed):
            pipe.gatherer.signal = tr.history_signal(t)
            pipe.gatherer.forecast = tr.forecast_signal(t, 6)
        enriched = handed.gatherer.enrich(infra)
        mon = wl.monitoring(t)
        a = own.run(app, infra, mon)
        with monkeypatch.context() as m:
            m.setattr(handed.gatherer, "enrich", None)
            b = handed.run(app, infra, mon, enriched=enriched)
        assert b.infra is enriched and a.infra == b.infra
        assert a.constraints == b.constraints
        assert (a.computation, a.communication) \
            == (b.computation, b.communication)
        la, lb = own.problem_for(a).lowering, handed.problem_for(b).lowering
        assert la.E.tobytes() == lb.E.tobytes()
        assert la.ci.tobytes() == lb.ci.tobytes()


# ---------------------------------------------------------------------------
# mu-decay ticks on a drifting continuum trace
# ---------------------------------------------------------------------------


def test_parity_across_mu_decay_ticks():
    """12 ticks of drifting profiles + carbon: constraints must match
    tick-for-tick while CK memory weights decay, drop below ``valid``,
    and are forgotten — and the two KBs must hold identical knowledge."""
    app, infra = _app(6), _infra()
    tr = CarbonTrace(REGION_PRESETS, hours=60, seed=3)
    wl = WorkloadTrace(app, seed=3)
    pa, pr = _pipes(alpha=0.6)
    outs_a = _drive(pa, app, infra, tr, wl, ticks=12)
    outs_r = _drive(pr, app, infra, tr, wl, ticks=12)
    for t, (oa, orf) in enumerate(zip(outs_a, outs_r)):
        assert oa.constraints == orf.constraints, f"tick {t}"
    # KB equivalence: the ArrayKB view materializes the same knowledge
    kb_a, kb_r = pa.kb, pr.kb
    for section in ("sk", "ik", "nk"):
        sa, sr = getattr(kb_a, section), getattr(kb_r, section)
        assert set(sa) == set(sr)
        for k in sr:
            assert sa[k] == sr[k], (section, k)
    assert set(kb_a.ck) == set(kb_r.ck)
    for k in kb_r.ck:
        assert kb_a.ck[k] == kb_r.ck[k]


def test_kb_view_reads_like_reference_kb():
    app, infra, mon = boutique.scenario(1)
    pipe = GreenConstraintPipeline(engine="array")
    out = pipe.run(app, infra, mon)
    key = out.constraints[0].key()
    assert key in pipe.kb.ck
    sc = pipe.kb.ck[key]
    assert sc.mu == 1.0 and sc.t == 1
    assert sc.constraint.generated_at == 1


def test_kb_persistence_roundtrip_via_arraykb(tmp_path):
    """ArrayKB.save writes the reference KB's JSON files; both loaders
    read either store with identical values."""
    app, infra, mon = boutique.scenario(1)
    pa, pr = _pipes()
    pa.run(app, infra, mon)
    pr.run(app, infra, mon)
    pa.kb.save(str(tmp_path / "a"))
    pr.kb.save(str(tmp_path / "r"))
    ka = KnowledgeBase.load(str(tmp_path / "a"))
    kr = KnowledgeBase.load(str(tmp_path / "r"))
    assert ka == kr
    # and the array loader round-trips to the identical KnowledgeBase
    assert ArrayKB.load(str(tmp_path / "r")).to_kb() == kr


# ---------------------------------------------------------------------------
# degenerate inputs
# ---------------------------------------------------------------------------


def test_empty_monitoring_yields_no_constraints():
    app, infra = _app(3), _infra()
    infra = infra.with_nodes([n.with_carbon(300.0) for n in infra.nodes])
    pa, pr = _pipes()
    oa = pa.run(app, infra, MonitoringData())
    orf = pr.run(app, infra, MonitoringData())
    assert oa.constraints == orf.constraints == []


def test_single_service_single_node():
    app = Application("t", (Service("s", flavours=(Flavour("f"),)),))
    infra = Infrastructure("t", (Node("n", carbon=500.0),))
    mon = MonitoringData(energy=(EnergySample("s", "f", 2.0),))
    pa, pr = _pipes()
    assert pa.run(app, infra, mon).constraints == \
        pr.run(app, infra, mon).constraints


def test_no_carbon_nodes_no_avoid_candidates():
    app = _app(3, links=False)
    infra = Infrastructure("t", (Node("n1"), Node("n2")))
    mon = MonitoringData(energy=(EnergySample("svc0", "large", 2.0),))
    pa, pr = _pipes()
    assert pa.run(app, infra, mon).constraints == \
        pr.run(app, infra, mon).constraints == []


@pytest.mark.parametrize("alpha", [0.0, 1.0])
def test_tau_edge_alphas(alpha):
    """alpha = 0: everything above the minimum survives; alpha = 1:
    nothing exceeds the maximum -> no constraints."""
    app, infra, mon = boutique.scenario(1)
    pa, pr = _pipes(alpha=alpha)
    oa = pa.run(app, infra, mon)
    orf = pr.run(app, infra, mon)
    assert oa.constraints == orf.constraints
    if alpha == 1.0:
        assert oa.constraints == []
    else:
        assert oa.constraints


@pytest.mark.parametrize("kw", [
    {"flavour_scope": "all"},
    {"tau_scope": "profiles"},
    {"flavour_scope": "all", "tau_scope": "profiles"},
])
def test_scope_variants_bit_match(kw):
    app, infra, mon = boutique.scenario(1)
    pa, pr = _pipes(**kw)
    assert pa.run(app, infra, mon).constraints == \
        pr.run(app, infra, mon).constraints


def test_timeshift_module_delegated_bit_match():
    """Non-builtin modules (TimeShift batch extension) run through their
    reference implementation inside the engine, in library order."""
    app, infra = _app(4, links=False), _infra()
    app = app.with_services([
        Service(s.component_id, flavours=s.flavours, delay_tolerance_h=6)
        for s in app.services])
    tr = CarbonTrace(REGION_PRESETS, hours=60, seed=1)
    wl = WorkloadTrace(app, seed=1)
    pa, pr = _pipes(library=ConstraintLibrary.with_batch_extension(),
                    alpha=0.5)
    for pipe in (pa, pr):
        pipe.gatherer.forecast = tr.forecast_signal(30, 8)
    outs_a = _drive(pa, app, infra, tr, wl, ticks=4, start=30)
    outs_r = _drive(pr, app, infra, tr, wl, ticks=4, start=30)
    for oa, orf in zip(outs_a, outs_r):
        assert oa.constraints == orf.constraints
        assert any(c.kind == "timeShift" for c in oa.constraints)


# ---------------------------------------------------------------------------
# incremental == full
# ---------------------------------------------------------------------------


def _engine_inputs(t, seed=7, S=8, N=5):
    """Deterministic drifting (computation, communication, infra)."""
    rng = np.random.default_rng((seed, t))
    prof = 0.05 * (1 + np.arange(S)) * rng.uniform(0.9, 1.1, S)
    comp = {(f"svc{i}", "large"): float(prof[i]) for i in range(S)}
    comm = {(f"svc{i}", "large", f"svc{(i + 1) % S}"): float(v)
            for i, v in enumerate(rng.uniform(0.01, 0.1, S))}
    ci = rng.uniform(100.0, 700.0, N)
    nodes = tuple(Node(f"n{j}", carbon=float(ci[j])) for j in range(N))
    return comp, comm, Infrastructure("t", nodes)


def test_incremental_matches_full_over_drift():
    app = _app(8, flavours=("large",), links=False)
    full = ConstraintEngine(kb=ArrayKB(), incremental=False)
    inc = ConstraintEngine(kb=ArrayKB(), incremental=True)
    for t in range(8):
        comp, comm, infra = _engine_inputs(t)
        a = full.run(app, infra, comp, comm, t + 1)
        b = inc.run(app, infra, comp, comm, t + 1)
        assert a.constraints == b.constraints, f"tick {t}"
    assert inc.last_stats.mode == "incremental"
    assert full.last_stats.mode == "full"


def test_incremental_skips_clean_candidates():
    """A tick with unchanged inputs re-scores nothing and reuses every
    cached constraint object."""
    app = _app(8, flavours=("large",), links=False)
    eng = ConstraintEngine(kb=ArrayKB(), incremental=True)
    comp, comm, infra = _engine_inputs(0)
    eng.run(app, infra, comp, comm, 1)
    assert eng.last_stats.mode == "rebuild"
    res = eng.run(app, infra, comp, comm, 2)
    st = eng.last_stats
    assert st.mode == "incremental"
    assert st.rescored == 0
    assert st.instantiated == 0 and st.reused == st.fresh
    # the output is still re-stamped with the new iteration
    assert all(c.generated_at == 2 for c in res.constraints
               if c.memory_weight == 1.0)


def test_structural_change_triggers_rebuild_and_matches():
    app = _app(6, flavours=("large",), links=False)
    full = ConstraintEngine(kb=ArrayKB(), incremental=False)
    inc = ConstraintEngine(kb=ArrayKB(), incremental=True)
    comp, comm, infra = _engine_inputs(0, S=6)
    full.run(app, infra, comp, comm, 1)
    inc.run(app, infra, comp, comm, 1)
    # a node appears: structure changes, outputs must stay identical
    comp, comm, infra = _engine_inputs(1, S=6, N=7)
    a = full.run(app, infra, comp, comm, 2)
    b = inc.run(app, infra, comp, comm, 2)
    assert inc.last_stats.mode == "rebuild"
    assert a.constraints == b.constraints


def test_tau_jax_backend_matches_numpy():
    vals = np.random.default_rng(0).uniform(0.0, 5.0, 257)
    for alpha in (0.0, 0.3, 0.8, 1.0):
        assert quantile_inf_tensor(vals, alpha, "jax") == \
            quantile_inf_tensor(vals, alpha, "numpy")


def test_engine_run_from_monitoring_matches_dict_path():
    app, infra, mon = boutique.scenario(1)
    from repro.core.energy import EnergyEstimator, EnergyMixGatherer

    infra_e = EnergyMixGatherer().enrich(infra)
    est = EnergyEstimator()
    e1 = ConstraintEngine(kb=ArrayKB())
    e2 = ConstraintEngine(kb=ArrayKB())
    a = e1.run(app, infra_e, est.computation_profiles(mon),
               est.communication_profiles(mon), 1)
    b = e2.run_from_monitoring(app, infra_e, mon, 1)
    assert a.constraints == b.constraints


def test_engine_switch_reference_roundtrip():
    """Flipping engines mid-stream converts the KB representation both
    ways without losing knowledge."""
    app, infra, mon = boutique.scenario(1)
    pipe = GreenConstraintPipeline(engine="array")
    out1 = pipe.run(app, infra, mon)
    pipe.engine = "reference"
    out2 = pipe.run(app, infra, mon)
    assert isinstance(pipe.kb, KnowledgeBase)
    pipe.engine = "array"
    out3 = pipe.run(app, infra, mon)
    assert {c.key() for c in out3.constraints} >= \
        {c.key() for c in out1.constraints}
    assert len(out2.constraints) == len(out3.constraints)
