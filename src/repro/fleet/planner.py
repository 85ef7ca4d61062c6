"""``plan_many``: the whole fleet as a few batched XLA programs.

Planning A tenants sequentially costs A planner dispatches per tick and
leaves the accelerator idle between them.  ``plan_many`` instead pads
every app into the pow2 bucket grid (:class:`~repro.core.problem.
BucketSpec`, now with an ``a`` apps axis), groups apps by padded shape,
and plans each group as ONE ``jit(vmap(planner_single))`` program over
the ``[A, ...]`` app axis — the same compile-cache discipline as the
single-app scheduler (one program per (backend, padded shape), phantom
rows masked inert), so a 1000-app fleet compiles a handful of programs
and reuses them every tick.

Coupling over the SHARED node capacity (see ``fleet.problem``):

* ``"none"``      — each app sees the full capacity.  Identical op
  sequence per app as ``GreenScheduler.plan`` (same ``planner_single``
  body, same padding semantics), so results are bit-identical to the
  sequential path whenever the arithmetic is exact.
* ``"waterfill"`` — one ``lax.scan`` over the (priority-sorted) app
  axis; each app plans against the capacity REMAINING after its
  predecessors, with in-scan warm-start revalidation.  Zero over-commit
  by construction.
* ``"price"``     — a few rounds of the uncoupled program with per-node
  CPU/RAM shadow prices folded into the constraint-penalty tensors
  (``green_pen * P_eff == green_pen * P + lam . req`` via an effective
  penalty scale), prices raised on over-committed nodes between rounds.
  Keeps full app parallelism; residual violations are reported.

The caller picks the devices (``plan_many(..., devices=...)``, default
every visible device).  On more than one, the uncoupled/price programs
are ``shard_map``-ed over the app axis (apps are embarrassingly
parallel), with the app axis padded by phantom apps to a multiple of the
device count; on one, the plain jit(vmap) program runs on that device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.lowering import (
    LoweredProblem,
    batched_lowered_emissions,
    lower_constraints,
    pad_lowering,
)
from repro.core.problem import (
    BucketSpec,
    PlacementProblem,
    PlanResult,
    PlanStats,
    _round_up,
)
from repro.core.scheduler import (
    COMPILE_CACHE,
    PLANNER_COMM_ARGC,
    GreenScheduler,
    _pad1,
    _static_feasibility,
    _warm_start_state,
    planner_single,
    plans_from_arrays,
)

from .problem import (
    FleetProblem,
    FleetResult,
    FleetStage,
    FleetStats,
    _CAP_EPS,
    empty_capacity_report,
    fleet_capacity_report,
)

__all__ = ["plan_many"]

# One jit program per communication-storage kind (shapes key jax's own
# cache; COMPILE_CACHE mirrors the signatures for observability).
_UNCOUPLED_CACHE: Dict[str, object] = {}
_WATERFILL_CACHE: Dict[str, object] = {}
_SHARDED_CACHE: Dict[Tuple, object] = {}

_WF_WARM_NOTE = ("warm start rejected (capacity claimed by "
                 "higher-priority tenants); rebuilt from scratch")


def _app_axes(argc: int) -> Tuple:
    """vmap in_axes over the app axis for ``planner_single``'s argument
    list: per-app tensors are mapped, infrastructure tensors and the
    objective weights are shared (one Infrastructure per fleet), and
    ``max_steps`` is mapped because it scales with each app's REAL
    service count."""
    return ((None, None, 0, 0)          # ci, ci_mean, E, order
            + (0,) * 5                  # warm state
            + (0,) * argc               # comm tensors
            + (0, 0, 0, 0, 0)           # P, A, stat_feas, cpu_req, ram_req
            + (None, None, 0, None)     # cpu_cap, ram_cap, must, cost
            + (None,) * 4               # objective weights
            + (0,))                     # max_steps


def _uncoupled_program(kind: str):
    if kind in _UNCOUPLED_CACHE:
        return _UNCOUPLED_CACHE[kind]
    import jax

    fn = jax.jit(jax.vmap(planner_single(kind),
                          in_axes=_app_axes(PLANNER_COMM_ARGC[kind])))
    _UNCOUPLED_CACHE[kind] = fn
    return fn


def _sharded_program(kind: str, devices: Tuple):
    """The uncoupled program shard_map-ed over the app axis: each device
    plans its slice of apps with the full (replicated) infrastructure."""
    key = (kind,) + tuple(d.id for d in devices)
    if key in _SHARDED_CACHE:
        return _SHARDED_CACHE[key]
    import jax
    from jax.sharding import Mesh, PartitionSpec

    axes = _app_axes(PLANNER_COMM_ARGC[kind])
    mesh = Mesh(np.array(devices), ("apps",))
    in_specs = tuple(
        PartitionSpec("apps") if a == 0 else PartitionSpec()
        for a in axes)
    fn = jax.jit(jax.shard_map(
        jax.vmap(planner_single(kind), in_axes=axes),
        mesh=mesh, in_specs=in_specs,
        out_specs=PartitionSpec("apps"), check_vma=False))
    _SHARDED_CACHE[key] = fn
    return fn


def _waterfill_program(kind: str):
    """Sequential waterfilling as one jit program: ``lax.scan`` over the
    app axis threading the shared (cpu_used, ram_used) node loads.  Each
    step revalidates the app's warm start against the REMAINING capacity
    (zeroing it when predecessors took the room), plans with the
    remaining capacity as the app's node caps, and commits the placed
    requirements into the carry — so the fleet can never over-commit a
    node the planner itself would have respected."""
    if kind in _WATERFILL_CACHE:
        return _WATERFILL_CACHE[kind]
    import jax
    import jax.numpy as jnp

    argc = PLANNER_COMM_ARGC[kind]
    single = planner_single(kind)

    def program(cpu_used0, ram_used0, ci, ci_mean, cpu_cap, ram_cap, cost,
                money_w, pref_w, emission_w, green_pen, stacked):
        def step(carry, xs):
            cpu_used, ram_used = carry
            E, order, wp, wf, wn, wcpu, wram = xs[:7]
            comm = xs[7:7 + argc]
            P, A, stat_feas, cpu_req, ram_req, must, max_steps = \
                xs[7 + argc:]
            rem_cpu = cpu_cap - cpu_used
            rem_ram = ram_cap - ram_used
            ok = ((wcpu <= rem_cpu).all() & (wram <= rem_ram).all())
            warm_reset = wp.any() & ~ok
            wp = wp & ok
            wf = jnp.where(ok, wf, 0)
            wn = jnp.where(ok, wn, 0)
            wcpu = jnp.where(ok, wcpu, 0.0)
            wram = jnp.where(ok, wram, 0.0)
            placed, fcur, ncur, skipped, infeas, fail_s = single(
                ci, ci_mean, E, order, wp, wf, wn, wcpu, wram, *comm,
                P, A, stat_feas, cpu_req, ram_req, rem_cpu, rem_ram,
                must, cost, money_w, pref_w, emission_w, green_pen,
                max_steps)
            # an infeasible app deploys nothing -> consumes nothing
            use = placed & ~infeas
            sel_cpu = jnp.take_along_axis(
                cpu_req, fcur[:, None], axis=1)[:, 0]
            sel_ram = jnp.take_along_axis(
                ram_req, fcur[:, None], axis=1)[:, 0]
            cpu_used = cpu_used.at[ncur].add(
                jnp.where(use, sel_cpu, 0.0))
            ram_used = ram_used.at[ncur].add(
                jnp.where(use, sel_ram, 0.0))
            return ((cpu_used, ram_used),
                    (placed, fcur, ncur, skipped, infeas, fail_s,
                     warm_reset))

        (cpu_f, ram_f), ys = jax.lax.scan(
            step, (cpu_used0, ram_used0), stacked)
        return cpu_f, ram_f, ys

    fn = jax.jit(program)
    _WATERFILL_CACHE[kind] = fn
    return fn


# ---------------------------------------------------------------------------
# Per-app preparation and chunk stacking
# ---------------------------------------------------------------------------


@dataclass
class _Prep:
    """One app, lowered+padded and ready to stack into an [A, ...] chunk."""

    idx: int                      # position in fleet.apps
    problem: PlacementProblem
    low: LoweredProblem           # real
    plow: LoweredProblem          # padded to the group dims
    dims: Tuple                   # (S_pad, F_pad, N_pad, L_pad)
    notes: List[str]
    warm: Tuple[np.ndarray, ...]  # padded 5-tuple
    order_pad: np.ndarray         # [S_pad]
    stat_feas: np.ndarray         # [S_pad, F_pad, N_pad] bool
    P: Optional[np.ndarray]       # None -> zero penalties
    A: Optional[np.ndarray]
    max_steps: int
    bucketed: bool
    out: Optional[Tuple[np.ndarray, ...]] = None
    extra_note: str = ""
    sig: Optional[Tuple] = None
    plan_time_s: float = 0.0
    compiled: bool = False


def _prep_app(idx: int, problem: PlacementProblem, cfg, bucket: BucketSpec,
              dims: Optional[Tuple] = None) -> _Prep:
    low = problem.lowering
    S, F, N = low.S, low.F, low.N
    L = low.comm.n_links if low.comm.kind == "sparse" else None

    notes: List[str] = []
    stat_feas_real = _static_feasibility(low)
    warm = None
    initial = problem.initial_assignment
    if initial is not None:
        warm, err = _warm_start_state(low, stat_feas_real, initial)
        if warm is None:
            notes.append(
                f"warm start rejected ({err}); rebuilt from scratch")
    if warm is None:
        warm = (np.zeros(S, dtype=bool), np.zeros(S, dtype=np.int64),
                np.zeros(S, dtype=np.int64), np.zeros(N), np.zeros(N))

    if dims is None:
        S_p, F_p, N_p, L_p, _ = bucket.pad_dims(S, F, N, L, 1)
        dims = (S_p, F_p, N_p, L_p)
    S_p, F_p, N_p, L_p = dims
    bucketed = dims != (S, F, N, L)
    plow = pad_lowering(low, S_p, F_p, N_p, L_p) if bucketed else low
    stat_feas = stat_feas_real if plow is low else _static_feasibility(plow)
    constraints = problem.constraints if cfg.use_green_constraints else ()
    P = A = None
    if constraints:
        P, A = lower_constraints(plow, constraints)
    order_pad = np.concatenate(
        [low.order, np.arange(S, S_p, dtype=low.order.dtype)]) \
        if S_p > S else low.order
    warm = (_pad1(warm[0], S_p), _pad1(warm[1], S_p), _pad1(warm[2], S_p),
            _pad1(warm[3], N_p), _pad1(warm[4], N_p))
    return _Prep(
        idx=idx, problem=problem, low=low, plow=plow, dims=dims,
        notes=notes, warm=warm, order_pad=order_pad, stat_feas=stat_feas,
        P=P, A=A,
        max_steps=cfg.local_search_rounds * max(1, S), bucketed=bucketed)


def _fleet_dims(probs: List[PlacementProblem],
                bucket: BucketSpec) -> Tuple:
    """One padded shape covering every app — required by the waterfill
    scan (all scan steps share one program shape).  When any app needs
    phantom COO edges, the shared S must exceed that app's real S so the
    phantom edges can point at a phantom service (same invariant
    ``BucketSpec.pad_dims`` enforces per problem)."""
    kinds = {p.lowering.comm.kind for p in probs}
    if len(kinds) > 1:
        raise ValueError(
            "waterfill coupling needs one communication backend across "
            f"the fleet, got {sorted(kinds)} — relower the apps with an "
            "explicit backend= choice")
    sparse = kinds.pop() == "sparse"
    S_p = F_p = N_p = 0
    L_p: Optional[int] = 0 if sparse else None
    for p in probs:
        low = p.lowering
        L = low.comm.n_links if sparse else None
        s, f, n, l, _ = bucket.pad_dims(low.S, low.F, low.N, L, 1)
        S_p, F_p, N_p = max(S_p, s), max(F_p, f), max(N_p, n)
        if sparse:
            L_p = max(L_p, l)
    if sparse and any(
            L_p > p.lowering.comm.n_links and S_p <= p.lowering.S
            for p in probs):
        S_p = _round_up(S_p + 1, bucket.s, bucket.s_floor)
    return (S_p, F_p, N_p, L_p)


def _chunk_args(chunk: List[_Prep], A_chunk: int,
                penalties: Optional[List[Tuple[np.ndarray, np.ndarray]]]):
    """Stack one chunk of same-shape preps into the planner's argument
    arrays, padding the app axis to ``A_chunk`` with INERT phantom apps:
    all-False feasibility and must masks (nothing placeable, nothing
    mandatory), zero warm state — a phantom row places nothing, consumes
    no capacity (critical under waterfilling), and stays feasible."""
    base = chunk[0]
    plow = base.plow
    S_p, F_p, N_p, _ = base.dims
    pad = A_chunk - len(chunk)
    zeros_P = np.zeros((S_p, F_p, N_p))
    zeros_A = np.zeros((S_p, S_p))
    no_feas = np.zeros((S_p, F_p, N_p), dtype=bool)
    no_must = np.zeros(S_p, dtype=bool)
    zero_warm = (np.zeros(S_p, dtype=bool), np.zeros(S_p, dtype=np.int64),
                 np.zeros(S_p, dtype=np.int64), np.zeros(N_p),
                 np.zeros(N_p))

    def stack(rows, phantom):
        if pad:
            rows = list(rows) + [phantom] * pad
        return np.stack(rows)

    if penalties is None:
        P_rows = [p.P if p.P is not None else zeros_P for p in chunk]
        A_rows = [p.A if p.A is not None else zeros_A for p in chunk]
    else:
        P_rows = [pen[0] for pen in penalties]
        A_rows = [pen[1] for pen in penalties]

    comm_cols = list(zip(*(p.plow.comm.planner_args() for p in chunk)))
    stacked = (
        (stack([p.plow.E for p in chunk], plow.E),
         stack([p.order_pad for p in chunk], base.order_pad))
        + tuple(stack([p.warm[i] for p in chunk], zero_warm[i])
                for i in range(5))
        + tuple(stack(col, col[0]) for col in comm_cols)
        + (stack(P_rows, zeros_P),
           stack(A_rows, zeros_A),
           stack([p.stat_feas for p in chunk], no_feas),
           stack([p.plow.cpu_req for p in chunk], plow.cpu_req),
           stack([p.plow.ram_req for p in chunk], plow.ram_req),
           stack([np.asarray(p.plow.must, dtype=bool) for p in chunk],
                 no_must),
           np.array([p.max_steps for p in chunk]
                    + [base.max_steps] * pad, dtype=np.int64))
    )
    ci_mean = float(np.asarray(base.low.ci).mean()) if base.low.N else 0.0
    shared = (np.asarray(plow.ci, dtype=float), ci_mean,
              np.asarray(plow.cpu_cap, dtype=float),
              np.asarray(plow.ram_cap, dtype=float),
              np.asarray(plow.cost, dtype=float))
    return shared, stacked


def _chunks(seq: List[_Prep], size: int):
    for i in range(0, len(seq), size):
        yield seq[i:i + size]


# ---------------------------------------------------------------------------
# Execution modes
# ---------------------------------------------------------------------------


@dataclass
class _Call:
    """One chunk of apps stacked into a program's arguments."""

    chunk: List[_Prep]
    args: Tuple
    sig: Tuple
    A_chunk: int


def _nbytes(args) -> int:
    """Bytes of a call's arguments: arrays by size, scalars as 8."""
    n = 0
    for a in args:
        if isinstance(a, tuple):
            n += _nbytes(a)
        else:
            n += a.nbytes if isinstance(a, np.ndarray) else 8
    return n


def _stack_group(kind: str, preps: List[_Prep], bucket: BucketSpec, cfg,
                 max_batch: int, n_dev: int, gp: float,
                 penalties: Optional[Callable] = None):
    """One same-shape group's calls of the uncoupled program, chunked
    along the app axis (phantom apps fill each chunk up to its bucket and
    a multiple of the devices); each chunk's ``penalties`` (a function of
    the chunk's preps) are folded and its arguments stacked only as the
    call is taken, so one chunk's arguments are held at a time."""
    argc = PLANNER_COMM_ARGC[kind]
    for chunk in _chunks(preps, max_batch):
        pens = penalties(chunk) if penalties else None
        A_chunk = -(-bucket.pad_apps(len(chunk)) // n_dev) * n_dev
        shared, stacked = _chunk_args(chunk, A_chunk, pens)
        ci, ci_mean, cpu_cap, ram_cap, cost = shared
        E, order = stacked[:2]
        warm = stacked[2:7]
        comm = stacked[7:7 + argc]
        P_s, A_s, sf_s, cpur, ramr, must_s, ms = stacked[7 + argc:]
        args = (ci, ci_mean, E, order, *warm, *comm, P_s, A_s, sf_s, cpur,
                ramr, cpu_cap, ram_cap, must_s, cost, cfg.money_weight,
                cfg.pref_weight, cfg.emission_weight, gp, ms)
        sig = ("fleet", kind, A_chunk) + chunk[0].dims + (
            (n_dev,) if n_dev > 1 else ())
        yield _Call(chunk, args, sig, A_chunk)


def _folded(calls, stage: FleetStage):
    """``calls`` one at a time, the time each took to fold and stack
    recorded as a ``fleet.fold`` under ``stage``."""
    it = iter(calls)
    while True:
        t0 = stage.children[-1].t1 if stage.children else stage.t0
        call = next(it, None)
        if call is None:
            return
        stage.children.append(
            FleetStage("fleet.fold", t0, time.perf_counter()))
        yield call


def _execute(fn, call: _Call, device, stats: FleetStats,
             stage: FleetStage, unpack, lead: Tuple = ()):
    """Run one call (``lead`` arguments first), copy its outputs to the
    host and hand them to ``unpack``, as ``fleet.dispatch``,
    ``fleet.wait`` and ``fleet.fetch`` under ``stage``; returns what
    ``unpack`` returns."""
    import jax

    # each part of a stage opens where the one before it closed
    t0 = stage.children[-1].t1 if stage.children else time.perf_counter()
    with jax.enable_x64(True), jax.default_device(device):
        out = fn(*lead, *call.args)
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    outs = [np.asarray(o) for o in jax.tree_util.tree_leaves(out)]
    rest = unpack(outs)
    t3 = time.perf_counter()
    stage.children += [
        FleetStage("fleet.dispatch", t0, t1,
                   {"h2d_bytes": _nbytes(lead) + _nbytes(call.args)}),
        FleetStage("fleet.wait", t1, t2),
        FleetStage("fleet.fetch", t2, t3,
                   {"d2h_bytes": sum(o.nbytes for o in outs)})]
    dt = t3 - t0
    compiled = COMPILE_CACHE.record(call.sig, dt)
    stats.calls += 1
    stats.compiles += int(compiled)
    stats.plan_time_s += dt
    stats.padded_apps += call.A_chunk - len(call.chunk)
    for prep in call.chunk:
        prep.sig, prep.plan_time_s, prep.compiled = call.sig, dt, compiled
    return rest


def _run_calls(kind: str, calls, devices: Tuple, stats: FleetStats,
               stage: FleetStage) -> None:
    """Run one group's calls of the uncoupled program in order; writes
    each prep's ``out`` row in place."""
    use_shard = len(devices) > 1
    fn = _sharded_program(kind, devices) if use_shard \
        else _uncoupled_program(kind)
    stats.sharded = use_shard
    for call in _folded(calls, stage):
        def unpack(outs, chunk=call.chunk):
            for i, prep in enumerate(chunk):
                prep.out = tuple(o[i] for o in outs)

        _execute(fn, call, devices[0], stats, stage, unpack)


def _stage(name: str, stats: FleetStats,
           t0: Optional[float] = None) -> FleetStage:
    """A new stage of the call, opening where the one before it closed."""
    if t0 is None:
        t0 = stats.stages[-1].t1 if stats.stages else time.perf_counter()
    stage = FleetStage(name, t0)
    stats.stages.append(stage)
    return stage


def _run_uncoupled(groups: Dict[Tuple, List[_Prep]], bucket: BucketSpec,
                   cfg, max_batch: int, devices: Tuple, stats: FleetStats,
                   penalties: Optional[Callable] = None,
                   green_pen: Optional[float] = None) -> FleetStage:
    """One pass of every group through the uncoupled program, each chunk
    folded with the ``penalties`` (a function of the chunk's preps) and
    stacked (``fleet.fold``) before its call; returns the pass's
    ``fleet.round`` stage, left open for the caller to close."""
    rnd = _stage("fleet.round", stats)
    gp = cfg.green_penalty if green_pen is None else green_pen
    for (kind, *_dims), preps in groups.items():
        _run_calls(kind, _stack_group(kind, preps, bucket, cfg, max_batch,
                                      len(devices), gp, penalties),
                   devices, stats, rnd)
    return rnd


def _run_waterfill(fleet: FleetProblem, preps: List[_Prep],
                   bucket: BucketSpec, cfg, max_batch: int, device,
                   stats: FleetStats,
                   used: Optional[Tuple[np.ndarray, np.ndarray]] = None
                   ) -> None:
    """Priority-ordered waterfill over all apps (one shared padded shape),
    chunked along the app axis with the node-load carry threaded across
    chunks host-side, from the loads ``used`` (default none).  The scan
    is sequential over apps, so it runs on one device."""
    rnd = _stage("fleet.round", stats)
    kind = preps[0].low.comm.kind
    order = [i for i in fleet.waterfill_order()]
    by_idx = {p.idx: p for p in preps}
    ordered = [by_idx[i] for i in order if i in by_idx]
    N_p = preps[0].dims[2]
    cpu_used = np.zeros(N_p) if used is None \
        else _pad1(np.asarray(used[0], dtype=float), N_p)
    ram_used = np.zeros(N_p) if used is None \
        else _pad1(np.asarray(used[1], dtype=float), N_p)
    fn = _waterfill_program(kind)

    def calls():
        for chunk in _chunks(ordered, max_batch):
            A_chunk = bucket.pad_apps(len(chunk))
            shared, stacked = _chunk_args(chunk, A_chunk, None)
            ci, ci_mean, cpu_cap, ram_cap, cost = shared
            args = (ci, ci_mean, cpu_cap, ram_cap, cost, cfg.money_weight,
                    cfg.pref_weight, cfg.emission_weight, cfg.green_penalty,
                    stacked)
            sig = ("fleet_wf", kind, A_chunk) + chunk[0].dims
            yield _Call(chunk, args, sig, A_chunk)

    for call in _folded(calls(), rnd):
        def unpack(outs, chunk=call.chunk):
            cpu_out, ram_out, *ys = outs
            for i, prep in enumerate(chunk):
                prep.out = tuple(y[i] for y in ys[:6])
                if ys[6][i]:
                    prep.extra_note = _WF_WARM_NOTE
            return cpu_out, ram_out

        cpu_used, ram_used = _execute(fn, call, device, stats, rnd, unpack,
                                      lead=(cpu_used, ram_used))
    rnd.t1 = rnd.children[-1].t1


def _loads_from_preps(preps: List[_Prep], N: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Fleet-total per-node loads from the current (real-sliced) planner
    outputs — the price iteration's subgradient input."""
    cpu = np.zeros(N)
    ram = np.zeros(N)
    for p in preps:
        placed, fcur, ncur = (a[:p.low.S] for a in p.out[:3])
        infeas = bool(p.out[4])
        if infeas or not placed.any():
            continue
        sel_cpu = np.take_along_axis(
            p.low.cpu_req, fcur[:, None], axis=1)[:, 0]
        sel_ram = np.take_along_axis(
            p.low.ram_req, fcur[:, None], axis=1)[:, 0]
        cpu += np.bincount(ncur[placed], weights=sel_cpu[placed],
                           minlength=N)
        ram += np.bincount(ncur[placed], weights=sel_ram[placed],
                           minlength=N)
    return cpu, ram


def _price_penalties(prep: _Prep, lam_cpu: np.ndarray, lam_ram: np.ndarray,
                     gp: float, gp_eff: float
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Fold per-node shadow prices into the app's penalty tensors.

    The planner scores ``green_pen * P`` — with ``green_pen`` replaced by
    ``gp_eff`` and ``P`` by ``(gp * P + lam . req) / gp_eff``, the scored
    term is exactly ``gp * P + lam_cpu[n] * cpu_req + lam_ram[n] *
    ram_req``: the original constraint penalties plus the Lagrangian
    capacity prices.  ``gp_eff = gp or 1`` keeps the fold well-defined
    when green constraints are off (gp == 0)."""
    plow = prep.plow
    lamc = _pad1(lam_cpu, plow.N)
    lamr = _pad1(lam_ram, plow.N)
    P0 = prep.P if prep.P is not None else 0.0
    P_eff = (gp * P0
             + lamc[None, None, :] * plow.cpu_req[:, :, None]
             + lamr[None, None, :] * plow.ram_req[:, :, None]) / gp_eff
    A0 = prep.A if prep.A is not None \
        else np.zeros((plow.S, plow.S))
    return P_eff, A0 * (gp / gp_eff)


def _run_price(fleet: FleetProblem, groups: Dict[Tuple, List[_Prep]],
               bucket: BucketSpec, cfg, max_batch: int, devices: Tuple,
               stats: FleetStats) -> None:
    ref = fleet.apps[0].lowering
    N = ref.N
    cpu_cap = np.asarray(ref.cpu_cap, dtype=float)
    ram_cap = np.asarray(ref.ram_cap, dtype=float)
    gp = cfg.green_penalty
    gp_eff = gp if gp != 0.0 else 1.0
    lam_cpu = np.zeros(N)
    lam_ram = np.zeros(N)
    all_preps = [p for preps in groups.values() for p in preps]
    for _ in range(max(1, fleet.price_rounds)):
        def pens(chunk):
            return [_price_penalties(p, lam_cpu, lam_ram, gp, gp_eff)
                    for p in chunk]

        rnd = _run_uncoupled(groups, bucket, cfg, max_batch, devices, stats,
                             penalties=pens, green_pen=gp_eff)
        stats.price_rounds += 1
        t_loads = rnd.children[-1].t1
        cpu_load, ram_load = _loads_from_preps(all_preps, N)
        stats.prices.append((lam_cpu.copy(), lam_ram.copy()))
        stats.loads.append((cpu_load, ram_load))
        exc_cpu = np.maximum(cpu_load - cpu_cap, 0.0)
        exc_ram = np.maximum(ram_load - ram_cap, 0.0)
        fits = bool((exc_cpu <= _CAP_EPS).all()
                    and (exc_ram <= _CAP_EPS).all())
        if not fits:
            lam_cpu += fleet.price_step * exc_cpu
            lam_ram += fleet.price_step * exc_ram
        rnd.t1 = time.perf_counter()
        rnd.children.append(FleetStage("fleet.loads", t_loads, rnd.t1))
        if fits:
            break


# ---------------------------------------------------------------------------
# Result materialization
# ---------------------------------------------------------------------------


def _finalize(prep: _Prep) -> PlanResult:
    """Slice one app's padded planner row back to its real shape and build
    the same B=1 :class:`PlanResult` the sequential path would — shared
    emissions reduction (``batched_lowered_emissions`` on the REAL
    lowering) and shared plan construction (``plans_from_arrays``)."""
    low = prep.low
    S = low.S
    placed, fcur, ncur, skipped, infeas, fail_s = prep.out
    placed_b = np.asarray(placed[:S], dtype=bool)[None]
    fcur_b = np.asarray(fcur[:S])[None]
    ncur_b = np.asarray(ncur[:S])[None]
    skipped_b = np.asarray(skipped[:S], dtype=bool)[None]
    infeas_b = np.asarray([bool(infeas)])
    fail_b = np.asarray([int(fail_s)])
    em_b = batched_lowered_emissions(
        low, placed_b, fcur_b, ncur_b,
        ci=np.asarray(low.ci, dtype=float)[None])
    notes = list(prep.notes)
    if prep.extra_note:
        notes.append(prep.extra_note)
    plans = plans_from_arrays(
        low, notes, placed_b, fcur_b, ncur_b, skipped_b, infeas_b,
        fail_b, low.order[None], em_b)
    L = low.comm.n_links if low.comm.kind == "sparse" else None
    stats = PlanStats(
        backend=low.comm.kind,
        shape=(1, S, low.F, low.N, L),
        padded_shape=(prep.sig[2],) + prep.dims if prep.sig else
        (1, S, low.F, low.N, L),
        signature=prep.sig or (), bucketed=prep.bucketed,
        compiled=prep.compiled,
        compile_time_s=prep.plan_time_s if prep.compiled else 0.0,
        plan_time_s=prep.plan_time_s,
        cache_hits=COMPILE_CACHE.hits, cache_misses=COMPILE_CACHE.misses)
    return PlanResult(
        problem=prep.problem, plans=plans, placed=placed_b, fcur=fcur_b,
        ncur=ncur_b,
        emissions_g=np.where(plans[0].feasible, em_b, np.inf),
        stats=stats)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def plan_many(fleet: FleetProblem,
              scheduler: Optional[GreenScheduler] = None, *,
              bucket: Optional[BucketSpec] = None,
              max_batch: int = 256,
              devices: Optional[Sequence] = None,
              used: Optional[Tuple[np.ndarray, np.ndarray]] = None
              ) -> FleetResult:
    """Plan every app of a :class:`FleetProblem` as batched programs.

    ``scheduler`` supplies the objective configuration (defaults to a
    fresh ``GreenScheduler()``); ``bucket`` the shape grid for both the
    per-app dims and the app axis (defaults to the scheduler's bucket,
    else pow2).  ``max_batch`` bounds apps per program execution —
    equal-size chunks reuse one compiled program, so the bound trades
    peak memory against dispatch count, not compiles.  ``devices`` are
    the jax devices the programs run on (default ``jax.devices()``): the
    uncoupled and price programs shard the app axis over all of them,
    the waterfill scan runs on the first.  ``used`` is a per-node
    ``(cpu, ram)`` load already committed outside the fleet; the
    waterfill coupling plans every app against the capacity left (the
    other couplings refuse it).

    Returns a :class:`FleetResult` with one B=1 ``PlanResult`` per app
    (same order as ``fleet.apps``), per-app emissions, the shared-node
    :class:`CapacityReport`, and call telemetry on ``.stats`` (its
    ``stages`` time the call: ``fleet.prepare``, one ``fleet.round`` per
    pass over the apps, ``fleet.finalize``).
    """
    t0 = time.perf_counter()
    if used is not None and fleet.coupling != "waterfill":
        raise ValueError("used= takes the waterfill coupling, not "
                         f"{fleet.coupling!r}")
    scheduler = scheduler if scheduler is not None else GreenScheduler()
    cfg = scheduler.config
    bucket = bucket if bucket is not None else (
        cfg.bucket if cfg.bucket is not None else BucketSpec())
    A = fleet.A
    stats = FleetStats(apps=A)
    results: List[Optional[PlanResult]] = [None] * A

    if A == 0:
        return FleetResult(
            fleet=fleet, results=[], emissions_g=np.zeros(0),
            capacity=empty_capacity_report(),
            coupling=fleet.coupling, stats=stats)

    import jax

    devices = tuple(jax.devices() if devices is None else devices)
    if not devices:
        raise ValueError("plan_many needs at least one device")
    prepare = _stage("fleet.prepare", stats, t0)

    # Shape-degenerate apps (no services / no nodes) take the scheduler's
    # host path — nothing to batch, nothing consumed.
    batched: List[Tuple[int, PlacementProblem]] = []
    for i, p in enumerate(fleet.apps):
        if p.lowering.S == 0 or p.lowering.N == 0:
            results[i] = scheduler.plan(p)
        else:
            batched.append((i, p))

    preps: List[_Prep] = []
    if batched and fleet.coupling == "waterfill":
        dims = _fleet_dims([p for _, p in batched], bucket)
        preps = [_prep_app(i, p, cfg, bucket, dims) for i, p in batched]
        stats.groups = 1
        stats.devices = 1
        prepare.t1 = time.perf_counter()
        _run_waterfill(fleet, preps, bucket, cfg, max_batch, devices[0],
                       stats, used)
    elif batched:
        preps = [_prep_app(i, p, cfg, bucket) for i, p in batched]
        groups: Dict[Tuple, List[_Prep]] = {}
        for prep in preps:
            key = (prep.low.comm.kind,) + prep.dims
            groups.setdefault(key, []).append(prep)
        stats.groups = len(groups)
        stats.devices = len(devices)
        prepare.t1 = time.perf_counter()
        if fleet.coupling == "price":
            _run_price(fleet, groups, bucket, cfg, max_batch, devices,
                       stats)
        else:
            rnd = _run_uncoupled(groups, bucket, cfg, max_batch, devices,
                                 stats)
            rnd.t1 = rnd.children[-1].t1
    else:
        prepare.t1 = time.perf_counter()

    finalize = _stage("fleet.finalize", stats)
    for prep in preps:
        results[prep.idx] = _finalize(prep)
    emissions = np.array([float(r.emissions_g[0]) for r in results]) \
        if results else np.zeros(0)
    capacity = fleet_capacity_report(fleet, results)
    result = FleetResult(
        fleet=fleet, results=results, emissions_g=emissions,
        capacity=capacity, coupling=fleet.coupling, stats=stats)
    finalize.t1 = time.perf_counter()
    return result
