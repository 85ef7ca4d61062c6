"""The plain reference of the adaptive loop, tick by tick.

It imports nothing of the program.  From the generated deployment, the
carbon and telemetry arrays and the traffic mix's settings it works out
what every tick of the loop has to decide:

* the constraint pass (paper Sect. 4.3-4.5): node CI is the mean of the
  last ``ci_window`` hours; AvoidNode candidates are every (service, its
  first flavour, node) with impact profile x CI, Affinity candidates
  every monitored link of a service's first flavour with impact energy x
  mean CI; a candidate above the ``alpha`` quantile of its kind's impacts
  (Eq. 5) is generated; the knowledge base gives a fresh constraint
  memory 1, decays the others by ``kb_decay``, forgets them under
  ``kb_forget`` and brings back those at ``kb_valid`` or more (Eq. 10);
  weights are impact over the largest impact, dropped under
  ``discard_below`` (Eq. 11);
* the planner's objective for forecast branch b,
  ``J_b = money x cost x CPU + pref x flavour rank + emission x E x CI_b
  + penalty x (w mu of the AvoidNode constraints a placement breaks)
  + sum over links whose ends sit on different nodes of (emission x
  mean CI_b x link energy + penalty x w mu of an Affinity constraint)``;
* each branch's plan: greedy construction in decreasing order of a
  service's largest energy profile, or the incumbent where there is one,
  then best single-service relocations while one improves ``J_b`` by
  more than ``1e-12``, at most ``local_search_rounds`` x S of them; moves
  and placements are taken in row-major order (service, flavour, node)
  where values tie;
* the what-if choice: the plan with the lowest emissions expected over
  the ensemble; the gate: switch when the expected saving over the
  horizon beats 2 g per migration, 0.5 g per restart and the
  hysteresis; the accounting at the hour's true CI.

Arithmetic runs in ``dtype``: float64 as the configuration states, or
float32 for the control.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .deployment import Microservices
from .reference import switch_charge

Assignment = Mapping[str, Tuple[str, str]]

EPS = 1e-12


@dataclass
class Decision:
    """What one tick decides, from a given incumbent."""

    plans: List[Optional[Dict[str, tuple]]]   # per branch; None: infeasible
    best: int
    cand: Optional[Dict[str, tuple]]
    switched: bool
    migrations: int
    restarts: int
    migration_g: float
    saving_g: float
    margin_g: float          # saving - (charge + hysteresis); 0 if no gate
    scale_g: float           # the incumbent's expected grams over the horizon
    committed: Optional[Dict[str, tuple]]


class Tensors:
    """A deployment's fixed arrays, services, flavours and nodes in the
    configuration's order (a flavour's index is its preference rank)."""

    def __init__(self, dep: Microservices, dtype):
        self.dtype = dtype
        self.sids = [s.sid for s in dep.services]
        self.flav = [[f.name for f in s.flavours] for s in dep.services]
        self.first = {s.sid: s.flavours[0].name for s in dep.services}
        self.nids = [n.nid for n in dep.nodes]
        self.regions = [n.region for n in dep.nodes]
        S, N = len(self.sids), len(self.nids)
        F = max(len(f) for f in self.flav)
        self.S, self.F, self.N = S, F, N
        self.sidx = {s: i for i, s in enumerate(self.sids)}
        self.nidx = {n: j for j, n in enumerate(self.nids)}
        self.fidx = [{f: k for k, f in enumerate(fl)} for fl in self.flav]
        self.valid = np.zeros((S, F), bool)
        self.cpu = np.zeros((S, F), dtype)
        self.ram = np.zeros((S, F), dtype)
        for i, s in enumerate(dep.services):
            for k, f in enumerate(s.flavours):
                self.valid[i, k] = True
                self.cpu[i, k], self.ram[i, k] = f.cpu, f.ram_gb
        self.cpu_cap = np.array([n.cpu for n in dep.nodes], dtype)
        self.ram_cap = np.array([n.ram_gb for n in dep.nodes], dtype)
        self.cost = np.array([n.cost for n in dep.nodes], dtype)

    def arrays(self, assign: Assignment):
        """(placed[S], f[S], n[S]) of an assignment."""
        placed = np.zeros(self.S, bool)
        f = np.zeros(self.S, np.int64)
        n = np.zeros(self.S, np.int64)
        for sid, (fname, nid) in assign.items():
            i = self.sidx[sid]
            placed[i], f[i], n[i] = True, self.fidx[i][fname], self.nidx[nid]
        return placed, f, n

    def assignment(self, placed, f, n) -> Dict[str, tuple]:
        return {self.sids[i]: (self.flav[i][int(f[i])], self.nids[int(n[i])])
                for i in range(self.S) if placed[i]}

    def profiles(self, E: Mapping, comm: Mapping):
        """``E[S, F]`` and ``K[S, F, S]`` (link energy) of one tick."""
        dt = self.dtype
        Em = np.zeros((self.S, self.F), dt)
        for (s, f), v in E.items():
            i = self.sidx[s]
            Em[i, self.fidx[i][f]] = v
        K = np.zeros((self.S, self.F, self.S), dt)
        for (s, f, z), v in comm.items():
            i, j = self.sidx[s], self.sidx[z]
            if i != j and f in self.fidx[i]:
                K[i, self.fidx[i][f], j] = v
        return Em, K


def quantile_inf(values: Sequence[float], alpha: float) -> float:
    """Eq. 5: the smallest sample x with F(x) >= alpha."""
    if not values:
        return math.inf
    xs = sorted(values)
    return xs[max(0, math.ceil(alpha * len(xs)) - 1)]


class ConstraintPass:
    """The constraint pass with its knowledge base, one tick after another
    from the runtime's first tick; gives each tick's penalty tensors
    ``P[S, F, N]`` (AvoidNode) and ``A[S, S]`` (Affinity), w x mu."""

    def __init__(self, tz: Tensors, series: Mapping[str, np.ndarray],
                 mix: Mapping):
        self.tz, self.series, self.mix = tz, series, mix
        self.ck: Dict[tuple, list] = {}     # key -> [impact, mu]
        self.t_next: Optional[int] = None

    def node_ci(self, t: int) -> List[float]:
        w = int(self.mix["ci_window"])
        out = []
        for r in self.tz.regions:
            recent = self.series[r][max(0, t + 1 - w): t + 1].tolist()
            out.append(sum(recent) / len(recent))
        return out

    def step(self, t: int, E: Mapping, comm: Mapping):
        if self.t_next is not None and t != self.t_next:
            raise ValueError(f"constraint pass at {t}, expected "
                             f"{self.t_next}: ticks run in order")
        self.t_next = t + 1
        tz, mix, dt = self.tz, self.mix, self.tz.dtype
        ci = [dt(c) for c in self.node_ci(t)]
        mean_ci = dt(sum(ci) / dt(len(ci)))
        avoid = []
        for s in tz.sids:
            f = tz.first[s]
            prof = E.get((s, f))
            if prof is None:
                continue
            for nid, c in zip(tz.nids, ci):
                avoid.append((dt(dt(prof) * c), ("avoidNode", s, f, nid)))
        affinity = [(dt(dt(e) * mean_ci), ("affinity", s, f, z))
                    for (s, f, z), e in comm.items()
                    if s != z and f == tz.first.get(s)]
        alpha = float(mix["alpha"])
        fresh = {}
        for cands in (avoid, affinity):
            tau = quantile_inf([c[0] for c in cands], alpha)
            fresh.update((k, v) for v, k in cands if v > tau)
        # Eq. 10: memory of the knowledge base
        for k, v in fresh.items():
            self.ck[k] = [v, 1.0]
        for k in list(self.ck):
            if k in fresh:
                continue
            self.ck[k][1] *= float(mix["kb_decay"])
            if self.ck[k][1] < float(mix["kb_forget"]):
                del self.ck[k]
        merged = [(v, 1.0, k) for k, v in fresh.items()]
        merged += [(v, mu, k) for k, (v, mu) in self.ck.items()
                   if k not in fresh and mu >= float(mix["kb_valid"])]
        P = np.zeros((tz.S, tz.F, tz.N), dt)
        A = np.zeros((tz.S, tz.S), dt)
        if not merged:
            return P, A
        top = max(v for v, _, _ in merged)
        if top <= 0:
            return P, A
        for v, mu, k in merged:      # Eq. 11
            w = v / top
            if w < float(mix["discard_below"]):
                continue
            kind, s, f, o = k
            i = tz.sidx[s]
            if kind == "avoidNode":
                P[i, tz.fidx[i][f], tz.nidx[o]] = dt(w) * dt(mu)
            else:
                A[i, tz.sidx[o]] = dt(w) * dt(mu)
        return P, A


class Objective:
    """``J_b`` of one tick for every branch, with its move grid."""

    def __init__(self, tz: Tensors, mix: Mapping, E, K, P, A, ci_b):
        dt = tz.dtype
        self.tz = tz
        self.E, self.K = E, K
        self.ci_b = np.asarray(ci_b, dt)
        self.mean_b = self.ci_b.mean(axis=1, dtype=dt)
        mw, pw, ew, gp = (dt(mix[k]) for k in (
            "money_weight", "pref_weight", "emission_weight",
            "green_penalty"))
        base = (mw * tz.cost[None, None, :] * tz.cpu[:, :, None]
                + pw * np.arange(tz.F, dtype=dt)[None, :, None]
                + gp * P)
        self.static = base[None] + ew * E[None, :, :, None] \
            * self.ci_b[:, None, None, :]                    # [B, S, F, N]
        has_link = K > 0
        self.W = (ew * self.mean_b[:, None, None, None] * K[None]
                  + gp * (A[:, None, :] * has_link)[None])    # [B, S, F, S]
        # the closest call of any choice made: the gap between the best
        # and the runner-up, over |J|; a gap within the program's
        # rounding could let it choose the other way
        self.closest = math.inf

    def _call(self, values, scale: float) -> None:
        v = np.sort(np.asarray(values, float).reshape(-1))
        v = v[np.isfinite(v)]
        if v.size > 1:
            self.closest = min(self.closest,
                               float(v[1] - v[0]) / max(abs(scale), 1e-300))

    def value(self, b: int, placed, f, n) -> float:
        tz = self.tz
        idx = np.arange(tz.S)
        tot = (self.static[b][idx, f, n] * placed).sum(dtype=tz.dtype)
        Wf = self.W[b][idx, f, :] * placed[:, None] * placed[None, :]
        return float(tot + (Wf * (n[:, None] != n[None, :])).sum(
            dtype=tz.dtype))

    def _score(self, b, placed, f, n):
        """score[s, f', n'] of each service's part of J_b at (f', n'), the
        others where they are."""
        tz = self.tz
        W = self.W[b]
        pf = placed.astype(tz.dtype)
        onehot = (n[:, None] == np.arange(tz.N)[None, :]) * pf[:, None]
        out = (W * pf[None, None, :]).sum(-1)[:, :, None] \
            - np.einsum("sfz,zn->sfn", W, onehot)
        Win = W[np.arange(tz.S), f, :] * pf[:, None]          # [Z, S]
        inn = Win.sum(0)[:, None] - np.einsum("zs,zn->sn", Win, onehot)
        return self.static[b] + out + inn[:, None, :]

    def deltas(self, b, placed, f, n):
        """``J_b`` after moving one service to (f', n') minus ``J_b`` now,
        inf where the move does not fit or changes nothing."""
        tz = self.tz
        score = self._score(b, placed, f, n)
        idx = np.arange(tz.S)
        cur = score[idx, f, n]
        cpu_l, ram_l = self.loads(placed, f, n)
        own = (n[:, None] == np.arange(tz.N)[None, :]) * placed[:, None]
        cpu_wo = cpu_l[None, :] - (tz.cpu[idx, f] * placed)[:, None] * own
        ram_wo = ram_l[None, :] - (tz.ram[idx, f] * placed)[:, None] * own
        fits = (tz.valid[:, :, None]
                & (cpu_wo[:, None, :] + tz.cpu[:, :, None]
                   <= tz.cpu_cap[None, None, :])
                & (ram_wo[:, None, :] + tz.ram[:, :, None]
                   <= tz.ram_cap[None, None, :]))
        same = ((np.arange(tz.F)[None, :, None] == f[:, None, None])
                & (np.arange(tz.N)[None, None, :] == n[:, None, None]))
        ok = fits & placed[:, None, None] & ~same
        return np.where(ok, score - cur[:, None, None], np.inf)

    def loads(self, placed, f, n):
        tz = self.tz
        idx = np.arange(tz.S)
        cpu = np.zeros(tz.N, tz.dtype)
        ram = np.zeros(tz.N, tz.dtype)
        np.add.at(cpu, n[placed], tz.cpu[idx, f][placed])
        np.add.at(ram, n[placed], tz.ram[idx, f][placed])
        return cpu, ram

    def greedy(self, b):
        tz = self.tz
        E = np.where(tz.valid, self.E, -np.inf).max(axis=1)
        order = np.argsort(-np.where(np.isfinite(E), E, 0.0), kind="stable")
        placed = np.zeros(tz.S, bool)
        f = np.zeros(tz.S, np.int64)
        n = np.zeros(tz.S, np.int64)
        cpu = np.zeros(tz.N, tz.dtype)
        ram = np.zeros(tz.N, tz.dtype)
        for s in order:
            fits = (tz.valid[s][:, None]
                    & (cpu[None, :] + tz.cpu[s][:, None] <= tz.cpu_cap)
                    & (ram[None, :] + tz.ram[s][:, None] <= tz.ram_cap))
            if not fits.any():
                return None
            score = np.where(fits, self._score(b, placed, f, n)[s], np.inf)
            self._call(score, score[fits].max())
            k = int(np.argmin(score))
            f[s], n[s] = divmod(k, tz.N)
            placed[s] = True
            cpu[n[s]] += tz.cpu[s, f[s]]
            ram[n[s]] += tz.ram[s, f[s]]
        return placed, f, n

    def local_search(self, b, placed, f, n, rounds: int):
        f, n = f.copy(), n.copy()
        for _ in range(rounds):
            d = self.deltas(b, placed, f, n)
            k = int(np.argmin(d))
            J = self.value(b, placed, f, n)
            self._call(np.append(d, -EPS), J)
            if not d.reshape(-1)[k] < -EPS:
                break
            s, rest = divmod(k, self.tz.F * self.tz.N)
            f[s], n[s] = divmod(rest, self.tz.N)
        return f, n

    def emissions(self, placed, f, n, ci) -> float:
        """Grams of one window at ``ci``: computation at the hosting node,
        transmission across nodes at the mean CI."""
        tz, dt = self.tz, self.tz.dtype
        ci = np.asarray(ci, dt)
        idx = np.arange(tz.S)
        comp = (placed * self.E[idx, f] * ci[n]).sum(dtype=dt)
        Kf = self.K[idx, f, :] * placed[:, None] * placed[None, :]
        cross = (Kf * (n[:, None] != n[None, :])).sum(dtype=dt)
        return float(comp + cross * ci.mean(dtype=dt))

    def expected(self, placed, f, n) -> float:
        dt = self.tz.dtype
        per = np.array([self.emissions(placed, f, n, c) for c in self.ci_b],
                       dt)
        return float(per.mean(dtype=dt))


class ReferenceLoop:
    """The adaptive loop of one runtime, from its first tick ``start``."""

    def __init__(self, dep: Microservices, mix: Mapping, series, carbon,
                 tel, start: int, dtype=np.float64):
        self.tz = Tensors(dep, dtype)
        self.mix, self.carbon, self.tel = mix, carbon, tel
        self.cpass = ConstraintPass(self.tz, series, mix)
        self.penalties: Dict[int, tuple] = {}
        self.t_next = start
        self._closest = math.inf
        self._last: Optional[Tuple[int, Objective]] = None
        self.closest_gate = math.inf

    @property
    def closest(self) -> float:
        """The closest call of the planner's choices so far (see
        :attr:`Objective.closest`)."""
        last = self._last[1].closest if self._last else math.inf
        return min(self._closest, last)

    def objective(self, t: int) -> Objective:
        if self._last is not None and self._last[0] == t:
            return self._last[1]
        dt = self.tz.dtype
        while self.t_next <= t:
            E, comm = self.tel.profiles(self.t_next)
            self.penalties[self.t_next] = self.cpass.step(
                self.t_next, E, comm)
            self.t_next += 1
        P, A = self.penalties[t]
        for k in [k for k in self.penalties if k < t]:
            del self.penalties[k]
        E, K = self.tz.profiles(*self.tel.profiles(t, dt))
        mix = self.mix
        ci_b = self.carbon.scenario_matrix(
            self.tz.regions, t, int(mix["horizon_h"]), int(mix["scenarios"]))
        obj = Objective(self.tz, mix, E, K, P, A, ci_b)
        self._closest = self.closest
        self._last = (t, obj)
        return obj

    def decide(self, t: int, prev: Optional[Assignment],
               follow: Optional[bool] = None) -> Decision:
        """Tick ``t`` from incumbent ``prev``.  ``follow`` settles a gate
        whose margin is within rounding of 0 the way the program did."""
        tz, mix, dt = self.tz, self.mix, self.tz.dtype
        obj = self.objective(t)
        rounds = int(mix["local_search_rounds"]) * tz.S
        warm = tz.arrays(prev) if prev else None
        plans, arrs, exp = [], [], []
        for b in range(obj.ci_b.shape[0]):
            start = warm if warm is not None else obj.greedy(b)
            if start is None:
                plans.append(None)
                arrs.append(None)
                exp.append(math.inf)
                continue
            placed = start[0]
            f, n = obj.local_search(b, placed, start[1], start[2], rounds)
            plans.append(tz.assignment(placed, f, n))
            arrs.append((placed, f, n))
            exp.append(obj.expected(placed, f, n))
        best = int(np.argmin(np.array(exp)))
        distinct = {}
        for p, e in zip(plans, exp):
            if p is not None:
                distinct[tuple(sorted(p.items()))] = e
        obj._call(list(distinct.values()), exp[best])
        cand = plans[best]
        out = Decision(plans, best, cand, False, 0, 0, 0.0, 0.0, 0.0, 0.0,
                       dict(prev) if prev else None)
        if cand is None:
            return out
        if not prev:
            out.switched, out.migrations, out.committed = \
                True, len(cand), dict(cand)
            return out
        if cand == dict(prev):
            return out
        e_prev = obj.expected(*warm)
        saving = float(dt(dt(e_prev - exp[best]) * dt(mix["horizon_h"])))
        out.scale_g = e_prev * float(mix["horizon_h"])
        moved, flapped = switch_charge(prev, cand)
        cost = float(dt(mix["migration_g"]) * dt(moved)
                     + dt(mix["restart_g"]) * dt(flapped))
        margin = saving - (cost + float(mix["hysteresis_g"]))
        out.saving_g, out.margin_g = saving, margin
        self.closest_gate = min(self.closest_gate,
                                abs(margin) / max(out.scale_g, 1e-300))
        tie = abs(margin) <= 1e-9 * max(1.0, abs(saving))
        switch = follow if (tie and follow is not None) else margin > 0
        if switch:
            out.switched, out.migrations, out.restarts = True, moved, flapped
            out.migration_g, out.committed = cost, dict(cand)
        return out

    def emissions(self, t: int, assign: Assignment) -> float:
        """Accounted grams of ``assign`` at hour ``t``'s true CI."""
        if not assign:
            return 0.0
        obj = self.objective(t)
        return obj.emissions(*self.tz.arrays(assign),
                             self.carbon.now(self.tz.regions, t))

