"""The operator's closed loop: ``ContinuumRuntime.tick`` back to back.

Each timed item is one hourly tick, timed from the caller: telemetry in,
constraints, lowering, the what-if planner over the forecast ensemble,
the hysteresis switch and the emissions accounting, until the plan is
committed.  The next tick starts when the previous one has returned, so
no queue forms.  Every tick of the window is judged by the reference:
each branch's plan, the choice among them, the switch and its charge,
the expected saving and the accounted emissions.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from .. import reference as ref
from ..adapter import assignment
from .continuum import Continuum


class Driver:
    label = "tick"

    def __init__(self, dep, mix, seed, devices, traced):
        from repro.obs import Observability

        self.mix = mix
        self.start = int(mix["start_hour"])
        warm = int(mix["warmup_ticks"])
        self.max_ticks = int(mix["max_ticks"])
        hours = self.start + warm + self.max_ticks + int(mix["horizon_h"]) + 25
        self.c = Continuum(dep, mix, seed, hours)
        self.obs = Observability() if traced else None
        self.rt = self.c.runtime(obs=self.obs)
        self.t = self.start
        for _ in range(warm):
            self.rt.tick(self.t)
            self.t += 1
        self.t_end = self.t + self.max_ticks
        self.incumbent0 = dict(self.rt.current or {})
        if self.obs is not None:
            self.obs.tracer.clear()
        self.rows: List[tuple] = []

    @property
    def exhausted(self) -> bool:
        return self.t >= self.t_end

    def step(self) -> None:
        rt, t = self.rt, self.t
        t0 = time.perf_counter()
        rec = rt.tick(t)
        dt = time.perf_counter() - t0
        res = rt.last_result if rec.replanned else None
        self.rows.append((t, rec, dict(rt.current or {}), res, dt))
        self.t += 1

    # -- results -----------------------------------------------------------

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        ms = 1e3 * np.array([r[4] for r in self.rows])
        return {"tick_ms_p50": float(np.percentile(ms, 50)),
                "tick_ms_p95": float(np.percentile(ms, 95))}

    def counts(self):
        return len(self.rows), sum(1 for r in self.rows if r[1].violations)

    def layer_inputs(self) -> Dict:
        spans: Dict[str, List[float]] = {}
        if self.obs is not None:
            for s in self.obs.tracer.spans:
                spans.setdefault(s.name, []).append(s.duration_s)
        return {"ticks": len(self.rows), "spans": spans}

    # -- correctness ---------------------------------------------------------

    def answers(self) -> List[tuple]:
        """Per tick: hour, committed placement, each branch's plan (None
        where infeasible), the chosen branch, switch, migrations,
        restarts, charge, accounted emissions, expected saving."""
        out = []
        for t, rec, committed, res, _ in self.rows:
            plans, best = [], -1
            if res is not None:
                plans = [assignment(p.placements) if p.feasible else None
                         for p in res.plans]
                best = int(res.best_index)
            out.append((t, committed, plans, best, rec.switched,
                        rec.migrations, rec.restarts, rec.migration_g,
                        rec.emissions_g, rec.expected_saving_g))
        return out

    def control_answers(self) -> List[tuple]:
        """The reference in float32 in the program's place: its own loop
        over the window's ticks, from the same incumbent."""
        loop = self.c.reference(0, self.start, np.float32)
        prev, out = self.incumbent0, []
        for t, *_ in self.answers():
            d = loop.decide(t, prev)
            committed = d.committed or {}
            out.append((t, committed, d.plans, d.best, d.switched,
                        d.migrations, d.restarts, d.migration_g,
                        loop.emissions(t, committed), d.saving_g))
            prev = committed
        return out

    def judge(self, answers) -> Dict[str, tuple]:
        lim = self.mix["limits"]
        c, prev = self.c, self.incumbent0
        loop = c.reference(0, self.start)
        gap, plan_errors, decisions, infeasible = 0.0, 0, 0, 0
        for (t, committed, plans, best, switched, migs, rsts, mig_g, em,
             saving) in answers:
            d = loop.decide(t, prev, follow=switched)
            # the planner: every branch's plan and the choice among them
            plan_errors += sum(p != q for p, q in zip(plans, d.plans))
            plan_errors += len(plans) != len(d.plans)
            if 0 <= best < len(plans) and plans[best] != d.cand:
                plan_errors += 1
            # the gate, the switch and its charge
            ok = (switched == d.switched
                  and committed == (d.committed or {})
                  and (migs, rsts) == (d.migrations, d.restarts)
                  and abs(mig_g - d.migration_g)
                  <= 1e-9 * max(1.0, d.migration_g))
            decisions += not ok
            # the expected saving and the accounting
            if saving or d.saving_g:
                gap = max(gap, ref.rel_gap(saving, d.saving_g, d.scale_g))
            gap = max(gap, ref.rel_gap(em, loop.emissions(t, committed)))
            if ref.violations(c.services, c.nodes, committed):
                infeasible += 1
            prev = committed
        self.closest = (loop.closest, loop.closest_gate)
        return {"emissions_gap": (gap, lim["emissions_gap"]),
                "plan_errors": (plan_errors, lim["plan_errors"]),
                "decision_errors": (decisions, lim["decision_errors"]),
                "infeasible": (infeasible, lim["infeasible"])}
