"""The fleet operator's closed loop: ``FleetRuntime.tick`` back to back.

Each timed item is one hourly tick of the whole fleet, timed from the
caller: every tenant's telemetry, constraint pass and lowering, one
``plan_many`` over all tenants with the capacity coupling the mix names
(its price rounds on the cell's chips, the app axis sharded over them),
the per-tenant gates, the capacity-keeping commit and the accounting.
Tenants are copies of the configured application; each has its own
telemetry stream, drawn from ``(seed, tenant)``, and its own peak hour.
The set-up runs ``warmup_ticks`` ticks, the first rollout among them.

Every timed tick is judged by ``bench/fleetref.py``: the committed
fleet's feasibility and summed capacity, the gates and the commit
replayed from the program's candidates and incumbents, and the
accounting; on ``judged_ticks`` ticks (the first and others drawn from
the seed) every tenant is re-planned through every price round.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from .. import adapter
from .. import reference as ref
from ..adapter import assignment
from ..fleetref import FleetReference, FleetTick
from ..signals import Carbon, Telemetry, carbon_series


def tenant_seed(seed: int, tenant: int) -> int:
    """The seed of one tenant's telemetry stream."""
    return int(np.random.SeedSequence([seed, 31, tenant]).generate_state(
        1, np.uint64)[0])


class Driver:
    label = "fleet"

    def __init__(self, dep, mix, seed, devices, traced):
        from repro.continuum import RuntimeConfig
        from repro.core.energy import EnergyMixGatherer
        from repro.core.kb import KBEnricher
        from repro.core.ranker import ConstraintRanker
        from repro.core.scheduler import GreenScheduler, SchedulerConfig
        from repro.fleet import FleetApp, FleetRuntime
        from repro.obs import Tracer

        self.dep, self.mix, self.seed = dep, mix, seed
        self.start = int(mix["start_hour"])
        warm = int(mix["warmup_ticks"])
        self.max_ticks = int(mix["max_ticks"])
        hours = self.start + warm + self.max_ticks + int(mix["horizon_h"]) + 25
        T = int(mix["tenants"])
        self.series = carbon_series(dep.regions, hours, seed)
        self.carbon = Carbon(self.series, seed)
        peaks = np.random.default_rng([seed, 41]).integers(0, 24, size=T)
        self.tels = [
            Telemetry(dataclasses.replace(
                dep, telemetry={**dep.telemetry, "peak_hour": float(p)}),
                hours, tenant_seed(seed, i))
            for i, p in enumerate(peaks)]
        app, infra = adapter.app_and_infra(dep)
        self.names = [f"tenant{i:04d}" for i in range(T)]
        self.tracer = Tracer() if traced else None
        self.frt = FleetRuntime(
            [FleetApp(name, app, adapter.TelemetryFeed(tel))
             for name, tel in zip(self.names, self.tels)],
            infra, self.carbon,
            config=RuntimeConfig(
                horizon_h=int(mix["horizon_h"]),
                hysteresis_g=float(mix["hysteresis_g"]),
                migration_g=float(mix["migration_g"]),
                restart_g=float(mix["restart_g"])),
            coupling=mix["coupling"],
            scheduler=GreenScheduler(SchedulerConfig(
                money_weight=float(mix["money_weight"]),
                pref_weight=float(mix["pref_weight"]),
                emission_weight=float(mix["emission_weight"]),
                green_penalty=float(mix["green_penalty"]),
                local_search_rounds=int(mix["local_search_rounds"]))),
            max_batch=int(mix["max_batch"]), devices=list(devices),
            tracer=self.tracer)
        for name in self.names:
            pl = self.frt.runtime(name).pipeline
            pl.alpha = float(mix["alpha"])
            pl.gatherer = EnergyMixGatherer(window=int(mix["ci_window"]))
            pl.enricher = KBEnricher(decay=float(mix["kb_decay"]),
                                     forget=float(mix["kb_forget"]),
                                     valid=float(mix["kb_valid"]))
            pl.ranker = ConstraintRanker(
                discard_below=float(mix["discard_below"]))
        self.t = self.start
        for _ in range(warm):
            self.frt.tick(self.t)
            self.t += 1
        self.t_end = self.t + self.max_ticks
        self.incumbent0 = self._committed()
        if self.tracer is not None:
            self.tracer.clear()
        self.rows: List[tuple] = []

    def _committed(self) -> List[Dict[str, tuple]]:
        return [dict(self.frt.runtime(n).current or {}) for n in self.names]

    @property
    def exhausted(self) -> bool:
        return self.t >= self.t_end

    def step(self) -> None:
        frt, t = self.frt, self.t
        t0 = time.perf_counter()
        frec = frt.tick(t)
        dt = time.perf_counter() - t0
        st = frt.last_result.stats
        cands = [assignment(r.plans[0].placements) if r.plans[0].feasible
                 else None for r in frt.last_result.results]
        rounds = [(lc, lr, cl, rl)
                  for (lc, lr), (cl, rl) in zip(st.prices, st.loads)]
        self.rows.append((t, frec, cands, self._committed(), rounds, dt))
        self.t += 1

    # -- results -----------------------------------------------------------

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        ms = 1e3 * np.array([r[5] for r in self.rows])
        return {"tick_ms_p50": float(np.percentile(ms, 50))}

    def counts(self):
        """Tenant-ticks attempted, and those refused (nothing deployed)."""
        return (len(self.rows) * len(self.names),
                sum(len(r[1].refused) for r in self.rows))

    def layer_inputs(self) -> Dict:
        spans: Dict[str, List[float]] = {}
        attrs: Dict[str, Dict[str, float]] = {}
        if self.tracer is not None:
            for s in self.tracer.spans:
                spans.setdefault(s.name, []).append(s.duration_s)
                into = attrs.setdefault(s.name, {})
                for k, v in s.attrs.items():
                    if isinstance(v, (int, float)):
                        into[k] = into.get(k, 0) + v
        return {"ticks": len(self.rows), "spans": spans, "attrs": attrs}

    # -- correctness ---------------------------------------------------------

    def answers(self) -> List[FleetTick]:
        """Per timed tick: each tenant's candidate, the gate's verdict,
        committed placement, switch, migrations, restarts, charge,
        accounted emissions and expected saving; the held, repaired and
        refused tenants; each price round's prices and loads."""
        idx = {n: i for i, n in enumerate(self.names)}
        out = []
        for t, frec, cands, committed, rounds, _ in self.rows:
            recs = [frec.records[n] for n in self.names]
            held = tuple(idx[n] for n in frec.held)
            refused = tuple(idx[n] for n in frec.refused)
            # the gate's verdict: switched, or held by the capacity, or a
            # first rollout with a plan that the capacity refused
            wants = [r.switched or i in held
                     or (i in refused and cands[i] is not None)
                     for i, r in enumerate(recs)]
            out.append(FleetTick(
                t, cands, wants, committed, [r.switched for r in recs],
                [r.migrations for r in recs], [r.restarts for r in recs],
                [r.migration_g for r in recs],
                [r.emissions_g for r in recs],
                [r.expected_saving_g for r in recs],
                held, tuple(idx[n] for n in frec.repaired), refused,
                rounds))
        return out

    def reference(self, dtype=np.float64) -> FleetReference:
        return FleetReference(self.dep, self.mix, self.series, self.carbon,
                              self.tels, self.start, dtype)

    def judged(self, n: int) -> set:
        """The timed ticks re-planned in full: the first, and others drawn
        from the seed."""
        k = min(int(self.mix["judged_ticks"]), n)
        rest = np.random.default_rng([self.seed, 51]).permutation(
            np.arange(1, n))[:max(0, k - 1)]
        return {0, *(int(i) for i in rest)} if n else set()

    def control_answers(self) -> List[FleetTick]:
        """The reference in float32 in the program's place: every tick's
        price rounds, gates, commit and accounting, from the same
        incumbents."""
        loop = self.reference(np.float32)
        prev, out = self.incumbent0, []
        for a in self.answers():
            cands, rounds = loop.price_plan(a.t, prev)
            d = loop.decide(a.t, prev, cands)
            d.rounds = rounds
            out.append(d)
            prev = d.committed
        return out

    def judge(self, answers: List[FleetTick]) -> Dict[str, tuple]:
        lim = self.mix["limits"]
        loop = self.reference()
        judged = self.judged(len(answers))
        prev = self.incumbent0
        gap, plan_errors, decisions, infeasible = 0.0, 0, 0, 0
        for k, a in enumerate(answers):
            if k in judged:
                cands, rounds = loop.price_plan(a.t, prev)
                plan_errors += sum(p != q for p, q in zip(a.cands, cands))
                plan_errors += len(a.cands) != len(cands)
                plan_errors += len(a.rounds) != len(rounds)
                plan_errors += sum(
                    not all(np.array_equal(x, y) for x, y in zip(r, q))
                    for r, q in zip(a.rounds, rounds))
            d = loop.decide(a.t, prev, a.cands, follow=a.wants)
            for i in range(len(a.cands)):
                ok = (a.wants[i] == d.wants[i]
                      and a.switched[i] == d.switched[i]
                      and a.committed[i] == d.committed[i]
                      and (a.migrations[i], a.restarts[i])
                      == (d.migrations[i], d.restarts[i])
                      and abs(a.charge_g[i] - d.charge_g[i])
                      <= 1e-9 * max(1.0, d.charge_g[i]))
                decisions += not ok
                if a.saving_g[i] or d.saving_g[i]:
                    gap = max(gap, ref.rel_gap(a.saving_g[i], d.saving_g[i],
                                               d.scale_g[i]))
                gap = max(gap, ref.rel_gap(
                    a.emissions_g[i],
                    loop.emissions(a.t, i, a.committed[i])))
            decisions += (a.held, a.repaired, a.refused) \
                != (d.held, d.repaired, d.refused)
            infeasible += loop.violations(a.committed)
            prev = a.committed
        self.closest = (loop.closest, loop.closest_gate)
        return {"emissions_gap": (gap, lim["emissions_gap"]),
                "plan_errors": (plan_errors, lim["plan_errors"]),
                "decision_errors": (decisions, lim["decision_errors"]),
                "infeasible": (infeasible, lim["infeasible"])}
