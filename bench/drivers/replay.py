"""The capacity planner's replay: ``run_scanned`` over a long trace.

A replay walks the carbon and telemetry trace of the run's seed in
consecutive chunks of ``chunk_ticks`` hours from ``start_hour``, each
chunk one ``run_scanned`` call on the same runtime: host staging of the
constraint pass, KB and lowering for every tick, one fused scan on the
device, the commit.  Each timed item is one chunk.  After ``chunks``
chunks the replay starts again from ``start_hour`` on a fresh runtime
whose forecast ensembles come from the next stream of the seed, so every
pass plans anew over the same hours.

The scan program's shape holds the largest number of live constraints of
a chunk's ticks, so chunks of other hours may need other programs.
Set-up replays one whole pass (stream 0) over the same hours, which
compiles or loads every program the window uses.

The reference replays a sample of the chunks, drawn from the seed, from
the placement the program held when each began, and compares every
tick's switch, charge, expected saving and accounted emissions and the
placement the chunk ends with.  A chunk that falls back to the eager
loop counts all its ticks failed.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from .. import reference as ref
from .continuum import Continuum


class Driver:
    label = "run_scanned"

    def __init__(self, dep, mix, seed, devices, traced):
        self.mix, self.seed = mix, seed
        self.T = int(mix["chunk_ticks"])
        self.K = int(mix["chunks"])
        self.start = int(mix["start_hour"])
        self.max_items = int(mix["max_items"])
        hours = self.start + self.T * self.K + int(mix["horizon_h"]) + 25
        self.c = Continuum(dep, mix, seed, hours)
        rt = self.c.runtime(stream=0)
        for k in range(self.K):
            rt.run_scanned(self.start + k * self.T, self.T)
        self.items: List[tuple] = []
        self.rt = None

    @property
    def exhausted(self) -> bool:
        return len(self.items) >= self.max_items

    def step(self) -> None:
        n = len(self.items)
        stream, k = n // self.K + 1, n % self.K
        t0 = time.perf_counter()
        if k == 0:
            self.rt = self.c.runtime(stream=stream)
        fell0 = len(self.rt.scanned_fallbacks)
        res = self.rt.run_scanned(self.start + k * self.T, self.T)
        dt = time.perf_counter() - t0
        self.items.append((stream, k, res.ticks, dict(res.final_assignment),
                           len(self.rt.scanned_fallbacks) > fell0, dt))

    # -- results -----------------------------------------------------------

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        ticks = sum(len(it[2]) for it in self.items)
        return {"replay_ticks_per_s": ticks / window_s}

    def counts(self):
        attempted = sum(len(it[2]) for it in self.items)
        failed = sum(len(it[2]) if it[4] else
                     sum(1 for r in it[2] if r.violations)
                     for it in self.items)
        return attempted, failed

    def layer_inputs(self) -> Dict:
        recs = [r for it in self.items if not it[4] for r in it[2]]
        return {"stage_s": [r.constraint_s for r in recs],
                "scan_s": [r.replan_s for r in recs]}

    # -- correctness ---------------------------------------------------------

    def answers(self) -> List[tuple]:
        """Per chunk: stream, index in its pass, per tick (hour, switch,
        migrations, restarts, charge, expected saving, accounted
        emissions), the final placement, whether it fell back."""
        return [(stream, k, [(r.t, r.switched, r.migrations, r.restarts,
                              r.migration_g, r.expected_saving_g,
                              r.emissions_g) for r in ticks], final, fell)
                for stream, k, ticks, final, fell, _ in self.items]

    def sample(self, answers) -> set:
        """(stream, chunk) pairs the reference replays: the first pass's
        first chunk, and the others with the probability that keeps the
        reference's ticks near ``judged_ticks``."""
        n = max(1, len(answers))
        p = min(1.0, float(self.mix["judged_ticks"]) / (n * self.T))
        rng = np.random.default_rng([self.seed, 31])
        keep = rng.random(n) < p
        return {(a[0], a[1]) for a, y in zip(answers, keep)
                if y or (a[0], a[1]) == (1, 0)}

    def control_answers(self) -> List[tuple]:
        """The reference in float32 in the program's place: its own loop
        through every sampled pass, chunk by chunk."""
        out, loop, prev = [], None, None
        for stream, k, per_tick, _, _ in self.answers():
            if k == 0:
                loop = self.c.reference(stream, self.start, np.float32)
                prev = None
            rows = []
            for t, *_ in per_tick:
                d = loop.decide(t, prev)
                prev = d.committed
                rows.append((t, d.switched, d.migrations, d.restarts,
                             d.migration_g, d.saving_g,
                             loop.emissions(t, prev or {})))
            out.append((stream, k, rows, dict(prev or {}), False))
        return out

    def _fold_closest(self, loop) -> None:
        self.closest = (min(self.closest[0], loop.closest),
                        min(self.closest[1], loop.closest_gate))

    def judge(self, answers) -> Dict[str, tuple]:
        mix, c = self.mix, self.c
        want = self.sample(answers)
        gap, plan_errors, decisions, infeasible = 0.0, 0, 0, 0
        loop, loop_stream, finals = None, None, {}
        self.closest = (math.inf, math.inf)
        for stream, k, per_tick, final, fell in answers:
            finals[(stream, k)] = final
            if fell:
                continue
            if ref.violations(c.services, c.nodes, final):
                infeasible += 1
            if (stream, k) not in want:
                continue
            if loop_stream != stream:
                if loop is not None:
                    self._fold_closest(loop)
                loop, loop_stream = c.reference(stream, self.start), stream
            prev = finals.get((stream, k - 1)) if k else None
            if k and prev is None:
                continue
            for t, switched, migs, rsts, mig_g, saving, em in per_tick:
                d = loop.decide(t, prev, follow=switched)
                ok = (switched == d.switched
                      and (migs, rsts) == (d.migrations, d.restarts)
                      and abs(mig_g - d.migration_g)
                      <= 1e-9 * max(1.0, d.migration_g))
                decisions += not ok
                if saving or d.saving_g:
                    gap = max(gap, ref.rel_gap(saving, d.saving_g,
                                               d.scale_g))
                prev = d.committed
                gap = max(gap, ref.rel_gap(em, loop.emissions(t, prev or {})))
            plan_errors += (prev or {}) != final
        if loop is not None:
            self._fold_closest(loop)
        lim = mix["limits"]
        return {"emissions_gap": (gap, lim["emissions_gap"]),
                "plan_errors": (plan_errors, lim["plan_errors"]),
                "decision_errors": (decisions, lim["decision_errors"]),
                "infeasible": (infeasible, lim["infeasible"])}
