"""The plain reference of the fleet tick: many tenants of one application
on a shared pool, their plans coupled by shadow prices on the pool's
capacity, committed without over-committing a machine.

It imports nothing of the program.  It builds on ``bench/refloop.py``
(the objective, the greedy construction and the local search of one
tenant) and ``bench/reference.py`` (feasibility and switch charges), and
works out, for a fleet of ``T`` tenants of one deployment whose
telemetry differs:

* the constraint pass of every tenant, tick after tick from the fleet's
  first tick: ``refloop.ConstraintPass`` with the tenant axis in front;
* the price rounds: every tenant planned alone (from its incumbent where
  it has one, else greedily) against the objective at the monitored node
  CI (the mean of the last ``ci_window`` hours) plus ``lam_cpu[n] x
  cpu_req + lam_ram[n] x ram_req``; the fleet's loads per machine summed
  over the feasible plans; where a machine is past its CPU or RAM the
  prices rise by ``price_step`` x the excess and the round repeats, at
  most ``price_rounds`` rounds; the last round's plans are the
  candidates;
* each tenant's gate: switch where the saving at the monitored CI over
  the horizon beats 2 g per migration, 0.5 g per restart and the
  hysteresis (a first rollout always switches);
* the commit: the gates' switches all together where the holders'
  incumbents and the switchers' candidates fit every machine; else,
  from every incumbent's load and in priority order (list order), each
  switch replaces its incumbent's load by its candidate's where no
  machine it loads goes past capacity, and holds where one would; a
  first rollout that does not fit is planned greedily, with the local
  search, into the capacity left, in priority order, and refused where
  it cannot be placed;
* the accounting at the hour's true CI.

Arithmetic runs in ``dtype``: float64 as the configuration states, or
float32 for the control.
"""
from __future__ import annotations

import copy
import math
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .deployment import Microservices
from .reference import CAPACITY_EPS, switch_charge
from .refloop import EPS, ConstraintPass, Objective, Tensors

Assignment = Mapping[str, Tuple[str, str]]


@dataclass
class FleetTick:
    """What one fleet tick answers; tenants by index in list order."""

    t: int
    cands: List[Optional[Dict[str, tuple]]]     # None: no feasible plan
    wants: List[bool]                           # the gate's verdict
    committed: List[Dict[str, tuple]]           # {} where nothing is
    switched: List[bool]
    migrations: List[int]
    restarts: List[int]
    charge_g: List[float]
    emissions_g: List[float]
    saving_g: List[float]
    held: Tuple[int, ...] = ()
    repaired: Tuple[int, ...] = ()
    refused: Tuple[int, ...] = ()
    # per price round: (lam_cpu, lam_ram, cpu_load, ram_load) per machine
    rounds: List[Tuple[np.ndarray, ...]] = field(default_factory=list)
    # the reference's own: each incumbent's expected grams over the
    # horizon, the scale of the saving's gap
    scale_g: List[float] = field(default_factory=list)


class FleetConstraintPass:
    """``refloop.ConstraintPass`` for ``T`` tenants at once: the same
    candidates, Eq. 5 threshold, knowledge base (Eq. 10) and weights
    (Eq. 11), each tenant with its own knowledge base."""

    def __init__(self, tz: Tensors, series, mix: Mapping, tenants: int,
                 links: Sequence[Tuple[str, str]]):
        self.tz, self.series, self.mix, self.T = tz, series, mix, tenants
        self.links = [(tz.sidx[s], tz.sidx[z]) for s, z in links]
        K = tz.S * tz.N + len(self.links)
        self.imp = np.zeros((tenants, K), tz.dtype)     # impact when fresh
        self.mu = np.zeros((tenants, K))                # memory
        self.present = np.zeros((tenants, K), bool)
        self.t_next: Optional[int] = None

    node_ci = ConstraintPass.node_ci

    def step(self, t: int, E_first: np.ndarray, comm: np.ndarray):
        """``E_first[T, S]``: each service's first-flavour profile;
        ``comm[T, L]``: each link's energy (both float64, as monitored).
        Returns ``P[T, S, F, N]`` and ``A[T, S, S]``, w x mu."""
        if self.t_next is not None and t != self.t_next:
            raise ValueError(f"constraint pass at {t}, expected "
                             f"{self.t_next}: ticks run in order")
        self.t_next = t + 1
        tz, mix, dt, T = self.tz, self.mix, self.tz.dtype, self.T
        S, N = tz.S, tz.N
        ci_list = [dt(c) for c in self.node_ci(t)]
        mean_ci = dt(sum(ci_list) / dt(len(ci_list)))
        ci = np.array(ci_list, dt)
        avoid = (E_first.astype(dt)[:, :, None]
                 * ci[None, None, :]).reshape(T, S * N)
        vals = np.concatenate([avoid, comm.astype(dt) * mean_ci], axis=1)
        alpha = float(mix["alpha"])
        fresh = np.zeros(vals.shape, bool)
        for lo, hi in ((0, S * N), (S * N, vals.shape[1])):
            n = hi - lo
            if n == 0:
                continue
            srt = np.sort(vals[:, lo:hi], axis=1)
            tau = srt[:, max(0, math.ceil(alpha * n) - 1)]
            fresh[:, lo:hi] = vals[:, lo:hi] > tau[:, None]
        # Eq. 10: memory of the knowledge base
        old = self.present & ~fresh
        self.mu[old] *= float(mix["kb_decay"])
        self.present[old & (self.mu < float(mix["kb_forget"]))] = False
        self.imp[fresh] = vals[fresh]
        self.mu[fresh] = 1.0
        self.present[fresh] = True
        merged = fresh | (self.present & (self.mu >= float(mix["kb_valid"])))
        top = np.where(merged, self.imp, -np.inf).max(axis=1)
        ok = merged & (top > 0)[:, None]
        safe = np.where(top > 0, top, dt(1))
        w = (self.imp / safe[:, None]).astype(dt)        # Eq. 11
        keep = ok & (w >= float(mix["discard_below"]))
        pw = np.where(keep, w * self.mu.astype(dt), dt(0))
        P = np.zeros((T, S, tz.F, N), dt)
        f0 = [tz.fidx[i][tz.first[s]] for i, s in enumerate(tz.sids)]
        P[:, np.arange(S), f0, :] = pw[:, :S * N].reshape(T, S, N)
        A = np.zeros((T, S, S), dt)
        for k, (i, j) in enumerate(self.links):
            A[:, i, j] = pw[:, S * N + k]
        return P, A


class PricedObjective(Objective):
    """``J`` of one tenant with the capacity prices added: ``lam_cpu[n] x
    cpu_req[s, f] + lam_ram[n] x ram_req[s, f]``."""

    def __init__(self, tz, mix, E, K, P, A, ci_b, lam_cpu, lam_ram):
        super().__init__(tz, mix, E, K, P, A, ci_b)
        dt = tz.dtype
        lam_c = np.asarray(lam_cpu, dt)
        lam_r = np.asarray(lam_ram, dt)
        if lam_c.any() or lam_r.any():
            self.static = self.static + (
                lam_c[None, None, :] * tz.cpu[:, :, None]
                + lam_r[None, None, :] * tz.ram[:, :, None])[None]


class FleetObjective:
    """``PricedObjective`` of ``T`` tenants at once, one branch each, with
    the tenant axis in front, and ``Objective.local_search`` run on all of
    them together: the same moves, taken in the same order, until no
    tenant has one that improves its ``J`` by more than ``EPS``."""

    def __init__(self, tz: Tensors, mix: Mapping, E, K, P, A, ci,
                 lam_cpu, lam_ram):
        dt = tz.dtype
        self.tz = tz
        ci = np.asarray(ci, dt)
        mean = ci.mean(dtype=dt)
        mw, pw, ew, gp = (dt(mix[k]) for k in (
            "money_weight", "pref_weight", "emission_weight",
            "green_penalty"))
        base = (mw * tz.cost[None, None, :] * tz.cpu[:, :, None]
                + pw * np.arange(tz.F, dtype=dt)[None, :, None]
                + gp * P)                                    # [T, S, F, N]
        self.static = base + ew * E[..., None] * ci[None, None, None, :]
        lam_c = np.asarray(lam_cpu, dt)
        lam_r = np.asarray(lam_ram, dt)
        if lam_c.any() or lam_r.any():
            self.static = self.static + (
                lam_c[None, None, :] * tz.cpu[:, :, None]
                + lam_r[None, None, :] * tz.ram[:, :, None])[None]
        self.W = (ew * mean * K
                  + gp * (A[:, :, None, :] * (K > 0)))       # [T, S, F, S]
        self.closest = math.inf

    def _deltas(self, sel, placed, f, n):
        """``Objective.deltas`` of the tenants ``sel``; also their ``J``."""
        tz, dt = self.tz, self.tz.dtype
        static, W = self.static[sel], self.W[sel]
        T, S, N = len(sel), tz.S, tz.N
        ti, si = np.arange(T)[:, None], np.arange(S)[None, :]
        pf = placed.astype(dt)
        onehot = (n[:, :, None] == np.arange(N)) * pf[:, :, None]
        out = (W * pf[:, None, None, :]).sum(-1)[..., None] \
            - (W.reshape(T, S * tz.F, S) @ onehot).reshape(T, S, tz.F, N)
        Win = W[ti, si, f, :] * pf[:, :, None]               # [T, Z, S]
        inn = Win.sum(1)[:, :, None] - Win.transpose(0, 2, 1) @ onehot
        score = static + out + inn[:, :, None, :]
        cur = score[ti, si, f, n]
        own = (n[:, :, None] == np.arange(N)) & placed[:, :, None]
        req_c = tz.cpu[si, f] * placed
        req_r = tz.ram[si, f] * placed
        cpu_l = (own * req_c[:, :, None]).sum(1, dtype=dt)
        ram_l = (own * req_r[:, :, None]).sum(1, dtype=dt)
        cpu_wo = cpu_l[:, None, :] - req_c[:, :, None] * own
        ram_wo = ram_l[:, None, :] - req_r[:, :, None] * own
        fits = (tz.valid[None, :, :, None]
                & (cpu_wo[:, :, None, :] + tz.cpu[None, :, :, None]
                   <= tz.cpu_cap)
                & (ram_wo[:, :, None, :] + tz.ram[None, :, :, None]
                   <= tz.ram_cap))
        same = ((np.arange(tz.F)[None, None, :, None] == f[:, :, None, None])
                & (np.arange(N) == n[:, :, None, None]))
        ok = fits & placed[:, :, None, None] & ~same
        d = np.where(ok, score - cur[:, :, None, None], np.inf)
        tot = (static[ti, si, f, n] * placed).sum(1, dtype=dt)
        Wf = W[ti, si, f, :] * placed[:, :, None] * placed[:, None, :]
        J = tot + (Wf * (n[:, :, None] != n[:, None, :])).sum((1, 2),
                                                              dtype=dt)
        return d.reshape(T, -1), J

    def local_search(self, placed, f, n, rounds: int, tenants=None):
        """The best single-service relocations of ``tenants`` (default
        all), from ``(placed, f, n)`` of shape ``[T, S]``, as
        ``Objective.local_search``."""
        f, n = f.copy(), n.copy()
        act = np.arange(len(f)) if tenants is None \
            else np.asarray(tenants, np.int64)
        for _ in range(rounds):
            if not act.size:
                break
            # tenants are independent: a few at a time keeps the grids
            # in cache
            act = np.concatenate([
                self._step(act[lo:lo + 32], placed, f, n)
                for lo in range(0, act.size, 32)])
        return f, n

    def _step(self, sel, placed, f, n):
        """One move of each tenant in ``sel`` that has an improving one,
        in place; returns those tenants."""
        tz = self.tz
        d, J = self._deltas(sel, placed[sel], f[sel], n[sel])
        k = np.argmin(d, axis=1)
        best = d[np.arange(len(sel)), k]
        # the closest call: the two smallest of the moves and -EPS
        two = np.sort(np.partition(
            np.concatenate([d, np.full((len(sel), 1), -EPS)], axis=1),
            1, axis=1)[:, :2].astype(float), axis=1)
        gaps = (two[:, 1] - two[:, 0]) / np.maximum(np.abs(J), 1e-300)
        gaps = gaps[np.isfinite(two[:, 1])]
        if gaps.size:
            self.closest = min(self.closest, float(gaps.min()))
        go = best < -EPS
        sel, k = sel[go], k[go]
        s, rest = np.divmod(k, tz.F * tz.N)
        f[sel, s], n[sel, s] = np.divmod(rest, tz.N)
        return sel


class _Profiles:
    """One tenant's profiles of one tick, for the accounting."""

    emissions = Objective.emissions

    def __init__(self, tz: Tensors, E, K):
        self.tz, self.E, self.K = tz, E, K


class FleetReference:
    """The fleet of ``tels`` (one ``Telemetry`` per tenant) on deployment
    ``dep``, from its first tick ``start``."""

    def __init__(self, dep: Microservices, mix: Mapping, series, carbon,
                 tels: Sequence, start: int, dtype=np.float64):
        self.tz = Tensors(dep, dtype)
        self.mix, self.series, self.carbon, self.tels = \
            mix, series, carbon, list(tels)
        self.T = len(self.tels)
        self.services = {s.sid: s for s in dep.services}
        self.nodes = {n.nid: n for n in dep.nodes}
        self.cpass = FleetConstraintPass(
            self.tz, series, mix, self.T,
            [(z[0], z[2]) for z in self.tels[0].edges] if self.tels else [])
        self.penalties: Dict[int, tuple] = {}
        self.t_next = start
        self.closest = math.inf          # the planner's closest call
        self.closest_gate = math.inf     # the gate's closest call
        self._prof: Optional[Tuple[int, list]] = None
        self._ci: Optional[Tuple[int, np.ndarray]] = None
        self._now: Optional[Tuple[int, np.ndarray]] = None

    # -- per tick --------------------------------------------------------

    def _penalties(self, t: int):
        tz = self.tz
        first = [(s, tz.first[s]) for s in tz.sids]
        while self.t_next <= t:
            E_first = np.empty((self.T, tz.S))
            comm = np.empty((self.T, len(self.cpass.links)))
            for i, tel in enumerate(self.tels):
                E, c = tel.profiles(self.t_next)
                E_first[i] = [E[k] for k in first]
                comm[i] = list(c.values())
            self.penalties[self.t_next] = self.cpass.step(
                self.t_next, E_first, comm)
            self.t_next += 1
        for k in [k for k in self.penalties if k < t]:
            del self.penalties[k]
        return self.penalties[t]

    def profiles(self, t: int) -> list:
        """Each tenant's ``(E[S, F], K[S, F, S])`` of tick ``t``."""
        if self._prof is None or self._prof[0] != t:
            dt = self.tz.dtype
            self._prof = (t, [self.tz.profiles(*tel.profiles(t, dt))
                              for tel in self.tels])
        return self._prof[1]

    def node_ci(self, t: int) -> np.ndarray:
        """The monitored CI the tick plans at: each machine's mean of the
        last ``ci_window`` hours."""
        if self._ci is None or self._ci[0] != t:
            dt = self.tz.dtype
            self._ci = (t, np.array([dt(c) for c in self.cpass.node_ci(t)],
                                    dt))
        return self._ci[1]

    def emissions(self, t: int, i: int, assign: Assignment,
                  ci=None) -> float:
        """Grams of tenant ``i``'s ``assign`` in tick ``t``'s window, at
        ``ci`` (default the hour's true CI)."""
        if not assign:
            return 0.0
        E, K = self.profiles(t)[i]
        if ci is None:
            if self._now is None or self._now[0] != t:
                self._now = (t, self.carbon.now(self.tz.regions, t))
            ci = self._now[1]
        return _Profiles(self.tz, E, K).emissions(
            *self.tz.arrays(assign), ci)

    # -- the planner -------------------------------------------------------

    def _plan(self, obj: Objective, tz: Tensors, warm):
        start = warm if warm is not None else obj.greedy(0)
        if start is None:
            return None
        rounds = int(self.mix["local_search_rounds"]) * tz.S
        f, n = obj.local_search(0, start[0], start[1], start[2], rounds)
        return start[0], f, n

    def price_plan(self, t: int, prevs: Sequence[Optional[Assignment]]):
        """Every tenant's candidate after the price rounds, and each
        round's ``(lam_cpu, lam_ram, cpu_load, ram_load)``."""
        tz, mix, dt = self.tz, self.mix, self.tz.dtype
        P, A = self._penalties(t)
        ci = self.node_ci(t)
        prof = self.profiles(t)
        E = np.stack([p[0] for p in prof])
        K = np.stack([p[1] for p in prof])
        warms = [tz.arrays(p) if p else None for p in prevs]
        rounds_max = int(mix["local_search_rounds"]) * tz.S
        lam_c, lam_r = np.zeros(tz.N, dt), np.zeros(tz.N, dt)
        rounds = []
        for _ in range(max(1, int(mix["price_rounds"]))):
            obj = FleetObjective(tz, mix, E, K, P, A, ci, lam_c, lam_r)
            starts = list(warms)
            for i in (i for i, w in enumerate(warms) if w is None):
                one = PricedObjective(tz, mix, *prof[i], P[i], A[i],
                                      ci[None], lam_c, lam_r)
                starts[i] = one.greedy(0)
                self.closest = min(self.closest, one.closest)
            ok = [i for i, st in enumerate(starts) if st is not None]
            placed = np.zeros((self.T, tz.S), bool)
            f = np.zeros((self.T, tz.S), np.int64)
            n = np.zeros((self.T, tz.S), np.int64)
            for i in ok:
                placed[i], f[i], n[i] = starts[i]
            f, n = obj.local_search(placed, f, n, rounds_max, ok)
            self.closest = min(self.closest, obj.closest)
            plans = [(placed[i], f[i], n[i]) if starts[i] is not None
                     else None for i in range(self.T)]
            cpu, ram = np.zeros(tz.N, dt), np.zeros(tz.N, dt)
            for plan in plans:
                if plan is not None:
                    c, r = self._arrays_load(*plan)
                    cpu += c
                    ram += r
            rounds.append((lam_c.copy(), lam_r.copy(), cpu, ram))
            exc_c = np.maximum(cpu - tz.cpu_cap, dt(0))
            exc_r = np.maximum(ram - tz.ram_cap, dt(0))
            if (exc_c <= CAPACITY_EPS).all() and (exc_r <= CAPACITY_EPS).all():
                break
            step = dt(mix["price_step"])
            lam_c = lam_c + step * exc_c
            lam_r = lam_r + step * exc_r
        cands = [tz.assignment(*p) if p is not None else None for p in plans]
        return cands, rounds

    # -- the gate and the commit -------------------------------------------

    def gate(self, t: int, i: int, prev: Optional[Assignment],
             cand: Optional[Assignment], follow: Optional[bool] = None):
        """``(switch, migrations, restarts, charge_g, saving_g, scale_g)``
        of tenant ``i``'s gate; ``follow`` settles a margin within
        rounding of 0 the way the program did."""
        mix, dt = self.mix, self.tz.dtype
        if cand is None:
            return False, 0, 0, 0.0, 0.0, 0.0
        if not prev:
            return True, len(cand), 0, 0.0, 0.0, 0.0
        if dict(cand) == dict(prev):
            return False, 0, 0, 0.0, 0.0, 0.0
        ci = self.node_ci(t)
        e_prev = self.emissions(t, i, prev, ci)
        e_cand = self.emissions(t, i, cand, ci)
        saving = float(dt(dt(e_prev - e_cand) * dt(mix["horizon_h"])))
        scale = e_prev * float(mix["horizon_h"])
        moved, flapped = switch_charge(prev, cand)
        cost = float(dt(mix["migration_g"]) * dt(moved)
                     + dt(mix["restart_g"]) * dt(flapped))
        margin = saving - (cost + float(mix["hysteresis_g"]))
        self.closest_gate = min(self.closest_gate,
                                abs(margin) / max(scale, 1e-300))
        tie = abs(margin) <= 1e-9 * max(1.0, abs(saving))
        switch = follow if (tie and follow is not None) else margin > 0
        if switch:
            return True, moved, flapped, cost, saving, scale
        return False, 0, 0, 0.0, saving, scale

    def _load(self, assign: Optional[Assignment]):
        tz = self.tz
        if not assign:
            return np.zeros(tz.N, tz.dtype), np.zeros(tz.N, tz.dtype)
        return self._arrays_load(*tz.arrays(assign))

    def _arrays_load(self, placed, f, n):
        tz = self.tz
        cpu = np.zeros(tz.N, tz.dtype)
        ram = np.zeros(tz.N, tz.dtype)
        np.add.at(cpu, n[placed], tz.cpu[np.arange(tz.S), f][placed])
        np.add.at(ram, n[placed], tz.ram[np.arange(tz.S), f][placed])
        return cpu, ram

    def commit(self, t: int, prevs, cands, wants):
        """``(committed, adopted, held, repaired, refused)`` of the
        commit, from the incumbents ``prevs``, the candidates and the
        gates' verdicts."""
        tz = self.tz
        cap = (tz.cpu_cap, tz.ram_cap)
        committed = [dict(p) if p else {} for p in prevs]
        switching = [i for i in range(self.T) if wants[i]]
        inc = [self._load(p) for p in prevs]
        new = {i: self._load(cands[i]) for i in switching}
        together = [sum(new[i][k] if i in new else inc[i][k]
                        for i in range(self.T)) for k in (0, 1)]
        held: List[int] = []
        repaired: List[int] = []
        if all((together[k] <= cap[k] + CAPACITY_EPS).all() for k in (0, 1)):
            adopted = set(switching)
        else:
            adopted = set()
            used = [sum(inc[i][k] for i in range(self.T)) for k in (0, 1)]
            deferred = []
            for i in switching:                         # list order
                trial = [used[k] - inc[i][k] + new[i][k] for k in (0, 1)]
                if all(((trial[k] <= cap[k] + CAPACITY_EPS)
                        | (trial[k] <= used[k])).all() for k in (0, 1)):
                    used = trial
                    adopted.add(i)
                elif not prevs[i]:
                    deferred.append(i)
                else:
                    held.append(i)
            for i in deferred:
                plan = self._repair(t, i, used)
                if plan is None:
                    continue
                committed[i] = plan
                repaired.append(i)
                c, r = self._load(plan)
                used = [used[0] + c, used[1] + r]
        for i in adopted:
            committed[i] = dict(cands[i])
        refused = [i for i in range(self.T) if not committed[i]]
        return (committed, adopted, tuple(held), tuple(repaired),
                tuple(refused))

    def _repair(self, t: int, i: int, used) -> Optional[Dict[str, tuple]]:
        """Tenant ``i`` planned alone into the capacity ``used`` leaves."""
        tz = copy.copy(self.tz)
        tz.cpu_cap = self.tz.cpu_cap - used[0]
        tz.ram_cap = self.tz.ram_cap - used[1]
        P, A = self._penalties(t)
        obj = Objective(tz, self.mix, *self.profiles(t)[i], P[i], A[i],
                        self.node_ci(t)[None])
        plan = self._plan(obj, tz, None)
        self.closest = min(self.closest, obj.closest)
        return tz.assignment(*plan) if plan is not None else None

    def decide(self, t: int, prevs, cands, follow=None) -> FleetTick:
        """Tick ``t`` from the incumbents ``prevs`` and the candidates
        ``cands``: the gates (``follow[i]`` settles a tie), the commit and
        the accounting."""
        gates = [self.gate(t, i, prevs[i], cands[i],
                           None if follow is None else follow[i])
                 for i in range(self.T)]
        wants = [g[0] for g in gates]
        committed, adopted, held, repaired, refused = self.commit(
            t, prevs, cands, wants)
        out = FleetTick(t, list(cands), wants, committed,
                        [False] * self.T, [0] * self.T, [0] * self.T,
                        [0.0] * self.T, [0.0] * self.T,
                        [g[4] for g in gates], held, repaired, refused,
                        scale_g=[g[5] for g in gates])
        for i, g in enumerate(gates):
            if i in repaired:
                out.switched[i] = True
                out.migrations[i] = len(committed[i])
            elif i in adopted:
                out.switched[i] = True
                out.migrations[i], out.restarts[i], out.charge_g[i] = \
                    g[1], g[2], g[3]
            out.emissions_g[i] = self.emissions(t, i, committed[i])
        return out

    def violations(self, committed: Sequence[Assignment]) -> int:
        """Broken guarantees of the committed fleet: per tenant, services
        left out or unknown; per machine, its summed load past its CPU or
        RAM."""
        tz = self.tz
        bad = 0
        cpu = np.zeros(tz.N)
        ram = np.zeros(tz.N)
        for assign in committed:
            bad += sum(1 for sid in self.services if sid not in assign)
            for sid, (f, n) in assign.items():
                svc = self.services.get(sid)
                if svc is None or n not in self.nodes \
                        or f not in {fl.name for fl in svc.flavours}:
                    bad += 1
                    continue
                fl = svc.flavour(f)
                cpu[tz.nidx[n]] += fl.cpu
                ram[tz.nidx[n]] += fl.ram_gb
        caps = np.array([[self.nodes[n].cpu, self.nodes[n].ram_gb]
                         for n in tz.nids])
        over = (cpu > caps[:, 0] + CAPACITY_EPS) \
            | (ram > caps[:, 1] + CAPACITY_EPS)
        return bad + int(over.sum())
