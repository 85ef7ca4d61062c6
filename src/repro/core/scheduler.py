"""Constraint-aware deployment scheduler (array-native core).

The paper delegates plan generation to an external constraint-based scheduler
([36]); we implement one as the required baseline so the whole pipeline is
runnable end-to-end.  The scheduler minimises a weighted objective

  J(assign) = money_weight   * monetary cost
            + pref_weight    * flavour-preference penalty (flavoursOrder)
            + emission_weight* emissions(assign)            [oracle only]
            + green_penalty  * sum over violated green constraints of
                               w_i * mu_i                   (soft constraints)

subject to hard requirements: subnet compatibility, node capacities
(CPU/RAM), availability.  Optional services may be dropped when no feasible
placement exists.

Two implementations share the objective:

* ``GreenScheduler`` — the array-native core with ONE public entrypoint:
  ``plan(problem: PlacementProblem) -> PlanResult``.  Greedy construction
  runs as a ``lax.scan`` over the service order and best-improvement local
  search as a ``lax.while_loop`` over the ``[S, F, N]`` single-relocation
  move grid, vmapped over the problem's scenario branches and compiled
  once per problem shape — an unbatched problem is simply B=1 on the same
  program.  Pairwise communication terms come from the lowering's
  pluggable backend: dense ``[S, F, S]`` einsums (``DenseLowering``) or
  COO segment sums (``SparseCommLowering``).  With a
  ``SchedulerConfig.bucket`` (:class:`~repro.core.problem.BucketSpec`),
  problem shapes are rounded up to bucket boundaries and padded with
  masked-out phantom entries so one compiled program serves every shape
  in the bucket; the planner compile cache tracks hits/misses/compile
  time per bucket signature (``compile_cache_stats()``), and every
  ``PlanResult`` carries its call's telemetry on ``.stats``.
* ``ReferenceScheduler`` — the legacy object-walking greedy +
  first-improvement local search, retained verbatim for equivalence testing
  and old-vs-new benchmarking.  ``reference_objective`` exposes its
  objective for any assignment.

Three standard profiles:
  * ``baseline``  — QoS/cost-driven, environment-blind (what today's
    schedulers do; the paper's motivation);
  * ``green``     — baseline + the generated green constraints;
  * ``oracle``    — directly minimises emissions (upper bound on savings).
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .library import subnet_compatible
from .lowering import (
    LoweredProblem,
    ScenarioBatch,
    batched_lowered_emissions,
    lower_constraints,
    pad_lowering,
)
from .problem import BucketSpec, PlacementProblem, PlanResult, PlanStats
from ..obs.registry import REGISTRY as _REGISTRY
from .types import (
    Affinity,
    Application,
    AvoidNode,
    Constraint,
    DeploymentPlan,
    Infrastructure,
    Placement,
    Service,
)

# Improvement threshold shared by both local searches (a move must beat the
# incumbent by more than this to be taken).
_EPS = 1e-12


@dataclass
class SchedulerConfig:
    money_weight: float = 1.0
    pref_weight: float = 1.0
    emission_weight: float = 0.0
    green_penalty: float = 5.0
    use_green_constraints: bool = True
    local_search_rounds: int = 50
    # Shape-bucketed compile cache: when set, problem shapes are rounded
    # up to the spec's bucket boundaries and the tensors padded with
    # masked-out phantom entries, so one XLA program serves every shape
    # in a bucket (None = exact shapes, one program per shape).
    bucket: Optional[BucketSpec] = None
    # Deprecated and ignored: the unified planner always runs the
    # jit-compiled path (kept so old configs keep constructing).
    use_jax: bool = False

    def __post_init__(self) -> None:
        if self.use_jax:
            warnings.warn(
                "SchedulerConfig.use_jax is deprecated and ignored: the "
                "unified planner always runs the jit-compiled path",
                DeprecationWarning, stacklevel=3)

    @classmethod
    def baseline(cls) -> "SchedulerConfig":
        return cls(use_green_constraints=False)

    @classmethod
    def green(cls) -> "SchedulerConfig":
        return cls(use_green_constraints=True)

    @classmethod
    def oracle(cls) -> "SchedulerConfig":
        return cls(money_weight=0.0, pref_weight=0.0, emission_weight=1.0,
                   use_green_constraints=False)


# ---------------------------------------------------------------------------
# Array-native scheduler
# ---------------------------------------------------------------------------


def _finish_move_deltas(xp, score, onehot, stat_feas, cpu_req, ram_req,
                        cpu_cap, ram_cap, placed, fcur, ncur,
                        cpu_load, ram_load):
    """Backend-independent tail of the move-grid evaluation: subtract the
    incumbent's score, mask capacity-infeasible cells (with the service's
    own load removed), unplaced services, and the incumbent cell."""
    S, F, N = score.shape
    cur = xp.take_along_axis(
        xp.take_along_axis(score, fcur[:, None, None], axis=1)[:, 0, :],
        ncur[:, None], axis=1)[:, 0]
    delta = score - cur[:, None, None]

    own_cpu = xp.take_along_axis(cpu_req, fcur[:, None], axis=1)[:, 0]
    own_ram = xp.take_along_axis(ram_req, fcur[:, None], axis=1)[:, 0]
    cpu_wo = cpu_load[None, :] - own_cpu[:, None] * onehot
    ram_wo = ram_load[None, :] - own_ram[:, None] * onehot
    feas = (stat_feas
            & (cpu_wo[:, None, :] + cpu_req[:, :, None]
               <= cpu_cap[None, None, :])
            & (ram_wo[:, None, :] + ram_req[:, :, None]
               <= ram_cap[None, None, :]))
    mask = feas & placed[:, None, None]
    incumbent = ((xp.arange(F)[None, :, None] == fcur[:, None, None])
                 & (xp.arange(N)[None, None, :] == ncur[:, None, None]))
    mask = mask & ~incumbent
    return xp.where(mask, delta, xp.inf)


def _dense_move_score(xp, static, W, placed, fcur, ncur):
    """Move-grid score[s, f, n] = J-contribution of s at (f, n), dense W."""
    S, F, N = static.shape
    placed_f = placed.astype(static.dtype)
    # onehot[z, n] = 1 iff service z is placed on node n
    onehot = (ncur[:, None] == xp.arange(N)[None, :]) * placed_f[:, None]

    # outgoing links s -> z: pay W[s, f, z] unless z sits on the target node
    t_out = (W * placed_f[None, None, :]).sum(-1)              # [S, F]
    out = t_out[:, :, None] - xp.einsum("sfz,zn->sfn", W, onehot)
    # incoming links z -> s under z's *current* flavour
    Wf = xp.take_along_axis(W, fcur[:, None, None], axis=1)[:, 0, :]
    Wf = Wf * placed_f[:, None]                                 # [Z, S]
    inn = Wf.sum(0)[:, None] - xp.einsum("zs,zn->sn", Wf, onehot)
    return static + out + inn[:, None, :], onehot               # [S, F, N]


def _sparse_move_score(xp, static, esrc, ef, edst, w, placed, fcur, ncur):
    """Same score as :func:`_dense_move_score` from a COO edge list — all
    pairwise terms are O(L) segment sums instead of O(S^2 F N) einsums."""
    S, F, N = static.shape
    dt = static.dtype
    placed_f = placed.astype(dt)
    onehot = (ncur[:, None] == xp.arange(N)[None, :]) * placed_f[:, None]

    w_out = w * placed_f[edst]                                  # [L]
    flat_sf = esrc * F + ef
    t_out = xp.zeros(S * F, dt).at[flat_sf].add(w_out).reshape(S, F)
    colloc = xp.zeros(S * F * N, dt).at[
        flat_sf * N + ncur[edst]].add(w_out).reshape(S, F, N)
    out = t_out[:, :, None] - colloc

    w_in = w * placed_f[esrc] * (ef == fcur[esrc])              # [L]
    inn_sum = xp.zeros(S, dt).at[edst].add(w_in)
    in_colloc = xp.zeros(S * N, dt).at[
        edst * N + ncur[esrc]].add(w_in).reshape(S, N)
    inn = inn_sum[:, None] - in_colloc
    return static + out + inn[:, None, :], onehot


def _move_deltas(xp, static, W, stat_feas, cpu_req, ram_req, cpu_cap,
                 ram_cap, placed, fcur, ncur, cpu_load, ram_load):
    """Delta objective of every single-relocation move, as one batched op
    (dense-W composition kept for external use and the dense jit path).

    Returns ``delta[s, f, n]`` = J(after moving s to (f, n)) - J(current),
    with +inf at infeasible moves, unplaced services, and the incumbent
    cell.  ``xp`` is ``numpy`` or ``jax.numpy`` — pure and shape-static.
    """
    score, onehot = _dense_move_score(xp, static, W, placed, fcur, ncur)
    return _finish_move_deltas(xp, score, onehot, stat_feas, cpu_req,
                               ram_req, cpu_cap, ram_cap, placed, fcur,
                               ncur, cpu_load, ram_load)


_PLAN_BATCH_CACHE: Dict[Tuple, object] = {}
_PLAN_SINGLE_CACHE: Dict[str, object] = {}

PLANNER_COMM_ARGC = {"dense": 2, "sparse": 4}


def planner_single(kind: str):
    """The pure single-branch planner function for communication-storage
    ``kind`` ("dense" | "sparse"), un-jitted.

    This is the exact function :func:`_batched_planner` vmaps+jits; it is
    exposed separately so callers that fuse planning into a LARGER jit
    program (the continuum megaloop's fused tick) embed the identical op
    sequence rather than re-deriving it.  Signature::

        single(ci, ci_mean, E, order,
               w_placed, w_fcur, w_ncur, w_cpu, w_ram,
               *comm_args,            # dense: K, has_link; sparse: COO 4
               P, A, stat_feas, cpu_req, ram_req, cpu_cap, ram_cap,
               must, cost, money_w, pref_w, emission_w, green_pen,
               max_steps) -> (placed, fcur, ncur, skipped, infeas, fail_s)

    Per branch: greedy construction is a ``lax.scan`` over the service
    order and local search a ``lax.while_loop`` over the single-relocation
    move grid.  The two kinds differ ONLY in how pairwise communication
    terms are scored (dense einsum vs COO segment sums); scoring values,
    row-major tie-breaks, improvement threshold, and must-deploy bailout
    are identical.
    """
    if kind in _PLAN_SINGLE_CACHE:
        return _PLAN_SINGLE_CACHE[kind]
    import jax
    import jax.numpy as jnp

    comm_argc = PLANNER_COMM_ARGC[kind]

    def single(ci, ci_mean, E, order, w_placed, w_fcur, w_ncur, w_cpu,
               w_ram, *rest):
        comm_args = rest[:comm_argc]
        (P, A, stat_feas, cpu_req, ram_req, cpu_cap, ram_cap, must, cost,
         money_w, pref_w, emission_w, green_pen, max_steps) = rest[comm_argc:]
        S, F, N = stat_feas.shape
        dt = ci.dtype
        static = (money_w * cost[None, None, :] * cpu_req[:, :, None]
                  + pref_w * jnp.arange(F, dtype=dt)[None, :, None]
                  + emission_w * E[:, :, None] * ci[None, None, :]
                  + green_pen * P)
        # the branch's REAL mean CI, passed explicitly: phantom bucket
        # nodes must not dilute the pairwise-transmission pricing
        wK = emission_w * ci_mean
        if kind == "dense":
            K, has_link = comm_args
            W = wK * K + green_pen * A[:, None, :] * has_link

            def greedy_comm(s, placed_f, fcur, ncur, onehot):
                w_out = W[s] * placed_f[None, :]                # [F, S]
                colloc = w_out @ onehot                         # [F, N]
                v_in = jnp.take_along_axis(
                    W[:, :, s], fcur[:, None], axis=1)[:, 0] * placed_f
                in_colloc = v_in @ onehot                       # [N]
                return ((w_out.sum(1)[:, None] - colloc)
                        + (v_in.sum() - in_colloc)[None, :])

            def move_score(placed, fcur, ncur):
                return _dense_move_score(jnp, static, W, placed, fcur, ncur)
        else:
            esrc, ef, edst, ek = comm_args
            w = wK * ek + green_pen * A[esrc, edst]

            def greedy_comm(s, placed_f, fcur, ncur, onehot):
                w_eff = w * (esrc == s) * placed_f[edst]        # [L]
                t_out = jnp.zeros(F, dt).at[ef].add(w_eff)
                colloc = jnp.zeros(F * N, dt).at[
                    ef * N + ncur[edst]].add(w_eff).reshape(F, N)
                w_in = (w * ((edst == s) & (ef == fcur[esrc]))
                        * placed_f[esrc])                       # [L]
                in_colloc = jnp.zeros(N, dt).at[ncur[esrc]].add(w_in)
                return ((t_out[:, None] - colloc)
                        + (w_in.sum() - in_colloc)[None, :])

            def move_score(placed, fcur, ncur):
                return _sparse_move_score(jnp, static, esrc, ef, edst, w,
                                          placed, fcur, ncur)

        def greedy_step(state, k):
            placed, fcur, ncur, cpu_load, ram_load, skipped, infeas, fail_s \
                = state
            s = order[k]
            feas = (stat_feas[s]
                    & (cpu_load[None, :] + cpu_req[s][:, None]
                       <= cpu_cap[None, :])
                    & (ram_load[None, :] + ram_req[s][:, None]
                       <= ram_cap[None, :]))
            placed_f = placed.astype(dt)
            onehot = ((ncur[:, None] == jnp.arange(N)[None, :])
                      * placed_f[:, None])                      # [S, N]
            score = static[s] + greedy_comm(s, placed_f, fcur, ncur, onehot)
            score = jnp.where(feas, score, jnp.inf)
            any_feas = feas.any()
            kk = jnp.argmin(score)   # row-major: flavour rank, node index
            f, n = kk // N, kk % N
            fresh = ~infeas & ~placed[s]
            do = any_feas & fresh
            placed = placed.at[s].set(placed[s] | do)
            fcur = fcur.at[s].set(jnp.where(do, f, fcur[s]))
            ncur = ncur.at[s].set(jnp.where(do, n, ncur[s]))
            cpu_load = cpu_load.at[n].add(
                jnp.where(do, cpu_req[s, f], 0.0))
            ram_load = ram_load.at[n].add(
                jnp.where(do, ram_req[s, f], 0.0))
            new_fail = ~any_feas & fresh & must[s]
            skipped = skipped.at[s].set(
                skipped[s] | (~any_feas & fresh & ~must[s]))
            fail_s = jnp.where(new_fail & (fail_s < 0), s, fail_s)
            infeas = infeas | new_fail
            return (placed, fcur, ncur, cpu_load, ram_load, skipped,
                    infeas, fail_s), None

        init = (w_placed, w_fcur, w_ncur, w_cpu, w_ram,
                jnp.zeros(S, dtype=bool), jnp.asarray(False),
                jnp.asarray(-1, dtype=order.dtype))
        with jax.named_scope("greedy"):
            (placed, fcur, ncur, cpu_load, ram_load, skipped, infeas,
             fail_s), _ = jax.lax.scan(greedy_step, init, jnp.arange(S))

        def ls_cond(st):
            return ~st[-1] & (st[-2] < max_steps)

        def ls_body(st):
            placed, fcur, ncur, cpu_load, ram_load, t, done = st
            score, onehot = move_score(placed, fcur, ncur)
            delta = _finish_move_deltas(
                jnp, score, onehot, stat_feas, cpu_req, ram_req, cpu_cap,
                ram_cap, placed, fcur, ncur, cpu_load, ram_load)
            kk = jnp.argmin(delta)
            improve = delta.reshape(-1)[kk] < -_EPS
            s = kk // (F * N)
            f = (kk % (F * N)) // N
            n = kk % N
            do = improve & ~done
            old_f, old_n = fcur[s], ncur[s]
            cpu_load = cpu_load.at[old_n].add(
                jnp.where(do, -cpu_req[s, old_f], 0.0))
            ram_load = ram_load.at[old_n].add(
                jnp.where(do, -ram_req[s, old_f], 0.0))
            cpu_load = cpu_load.at[n].add(jnp.where(do, cpu_req[s, f], 0.0))
            ram_load = ram_load.at[n].add(jnp.where(do, ram_req[s, f], 0.0))
            fcur = fcur.at[s].set(jnp.where(do, f, fcur[s]))
            ncur = ncur.at[s].set(jnp.where(do, n, ncur[s]))
            return (placed, fcur, ncur, cpu_load, ram_load, t + 1,
                    done | ~improve)

        # infeasible branches skip local search; under vmap the while body
        # no-ops once done is set.
        with jax.named_scope("local_search"):
            placed, fcur, ncur, cpu_load, ram_load, _, _ = \
                jax.lax.while_loop(
                    ls_cond, ls_body,
                    (placed, fcur, ncur, cpu_load, ram_load,
                     jnp.asarray(0), infeas))
        return placed, fcur, ncur, skipped, infeas, fail_s

    _PLAN_SINGLE_CACHE[kind] = single
    return single


# The planner's three packed argument buffers, in call order.
_F64, _I64, _BOOL = 0, 1, 2
_PLAN_BUFFER_DTYPES = (np.float64, np.int64, np.bool_)


def _plan_layout(sig: Tuple) -> Tuple[Tuple, Tuple[int, int, int]]:
    """Where each argument of :func:`planner_single` lies in the packed
    buffers of one planner call.

    Returns ``(slots, sizes)``: one ``(buffer, offset, shape)`` per
    argument in signature order, ``buffer`` indexing ``(float64, int64,
    bool)``, and each buffer's length.  A function of the padded
    signature ``(kind, B, S, F, N, L)`` alone, so every call with one
    compile key shares one layout and one program.
    """
    kind, B, S, F, N, L = sig
    comm = (((_F64, (S, F, S)), (_BOOL, (S, F, S)))     # K, has_link
            if kind == "dense"
            else ((_I64, (L,)),) * 3 + ((_F64, (L,)),))  # src, fidx, dst, k
    fields = (
        (_F64, (B, N)), (_F64, (B,)), (_F64, (B, S, F)),  # ci, ci_mean, E
        (_I64, (B, S)),                                    # order
        (_BOOL, (S,)), (_I64, (S,)), (_I64, (S,)),        # warm start
        (_F64, (N,)), (_F64, (N,)),
        *comm,
        (_F64, (S, F, N)), (_F64, (S, S)), (_BOOL, (S, F, N)),  # P, A, mask
        (_F64, (S, F)), (_F64, (S, F)), (_F64, (N,)), (_F64, (N,)),
        (_BOOL, (S,)), (_F64, (N,)),                       # must, cost
        *((_F64, ()),) * 4,                                # the weights
        (_I64, ()))                                        # max_steps
    sizes = [0, 0, 0]
    slots = []
    for buf, shape in fields:
        slots.append((buf, sizes[buf], shape))
        sizes[buf] += math.prod(shape)
    return tuple(slots), tuple(sizes)


def _pack_plan_args(sig: Tuple, args: Sequence) -> List[np.ndarray]:
    """Copy the planner's arguments into its three packed buffers.

    Values keep their dtype class: floats stay float64, integers stay
    exact int64, booleans stay bool (a float into an integer buffer
    raises)."""
    slots, sizes = _plan_layout(sig)
    bufs = [np.empty(n, dt) for n, dt in zip(sizes, _PLAN_BUFFER_DTYPES)]
    for a, (buf, off, shape) in zip(args, slots, strict=True):
        a = np.asarray(a)
        if a.shape != shape:
            raise ValueError(
                f"planner argument of shape {a.shape}, layout {shape}")
        np.copyto(bufs[buf][off:off + a.size], a.reshape(-1),
                  casting="same_kind")
    return bufs


def _unpack_plan_out(out: np.ndarray, B: int, S: int, S_p: int,
                     fail_dtype) -> Tuple[np.ndarray, ...]:
    """Split the planner's packed ``[B_p, 4 S_p + 2]`` int32 output into
    ``(placed, fcur, ncur, skipped, infeas, fail_s)`` for the first ``B``
    branches and ``S`` services, in the dtypes :func:`planner_single`
    returns them (``fail_s`` in the order's dtype)."""
    out = out[:B]
    return (out[:, :S] != 0,
            out[:, S_p:S_p + S].astype(np.int64),
            out[:, 2 * S_p:2 * S_p + S].astype(np.int64),
            out[:, 3 * S_p:3 * S_p + S] != 0,
            out[:, 4 * S_p] != 0,
            out[:, 4 * S_p + 1].astype(fail_dtype))


def _batched_planner(sig: Tuple):
    """One jit-compiled program planning B scenario branches at once.

    Built lazily (jax import deferred) and cached per padded signature
    ``sig = (kind, B, S, F, N, L)`` so every adaptive-loop tick with
    unchanged problem shapes reuses the compiled executable — the problem
    tensors are ARGUMENTS, not closed-over constants, so drifting
    profiles/forecasts never retrace.  The vmapped body is exactly
    :func:`planner_single`.

    The program takes the arguments as three packed buffers (float64,
    int64, bool; :func:`_plan_layout`), unpacked with static slices, and
    returns one ``[B, 4 S + 2]`` int32 array: ``placed``, ``fcur``,
    ``ncur`` and ``skipped`` as blocks of ``S`` columns, then ``infeas``
    and ``fail_s`` (:func:`_unpack_plan_out`).  Each array that crosses
    the host-device boundary costs a fixed latency whatever its size, so
    one call moves three arrays in and one out.
    """
    if sig in _PLAN_BATCH_CACHE:
        return _PLAN_BATCH_CACHE[sig]
    import jax
    import jax.numpy as jnp

    comm_argc = PLANNER_COMM_ARGC[sig[0]]
    batched = jax.vmap(
        planner_single(sig[0]),
        in_axes=(0, 0, 0, 0) + (None,) * (5 + comm_argc + 14))
    slots, _ = _plan_layout(sig)

    # the function's name is the program's: a device trace names the
    # module ``jit_green_planner``
    def green_planner(f64, i64, flags):
        bufs = (f64, i64, flags)
        args = [bufs[b][off:off + math.prod(shape)].reshape(shape)
                for b, off, shape in slots]
        placed, fcur, ncur, skipped, infeas, fail_s = batched(*args)
        return jnp.concatenate(
            [placed, fcur, ncur, skipped, infeas[:, None], fail_s[:, None]],
            axis=1, dtype=jnp.int32)

    fn = jax.jit(green_planner)
    _PLAN_BATCH_CACHE[sig] = fn
    return fn


# ---------------------------------------------------------------------------
# Planner compile cache: one entry per (backend kind, padded program shape).
# The jit executable itself lives in jax's cache; this registry mirrors its
# keys so hit/miss/compile-time are observable (PlanResult.stats, the
# BENCH_scheduler.json compile_cache section, and the CI hit-rate gate).
# ---------------------------------------------------------------------------


class PlannerCompileCache:
    """Counters over the planner's XLA program signatures.

    A *miss* is a signature this process has never planned before — the
    call that pays the program build.  That is a real XLA compile unless
    jax's persistent compilation cache (``jax_compilation_cache_dir``) is
    enabled, in which case a miss may be served by deserializing a
    previously persisted program — much faster, but still counted as a
    miss (the counters track per-process program builds, not cold
    compiles).  ``reset_counters()`` zeroes the windowed counters but
    keeps the signature registry: replanning a known shape after a reset
    is still a hit (no rebuild happens).
    """

    def __init__(self) -> None:
        self.signatures: Dict[Tuple, Dict[str, float]] = {}
        self.reset_counters()

    def reset_counters(self) -> None:
        self.calls = 0
        self.hits = 0
        self.misses = 0
        self.compile_time_s = 0.0

    def record(self, sig: Tuple, plan_time_s: float) -> bool:
        """Account one planner call; returns True when it compiled.

        Every call is mirrored onto the global metrics registry
        (``planner.compile.{calls,hits,misses,time_s}``) — read those
        with ``repro.obs.metrics_scope`` for bleed-free deltas instead
        of resetting these process-global counters.
        """
        self.calls += 1
        _REGISTRY.inc("planner.compile.calls")
        entry = self.signatures.get(sig)
        if entry is None:
            self.misses += 1
            self.compile_time_s += plan_time_s
            self.signatures[sig] = {"calls": 1,
                                    "compile_time_s": plan_time_s}
            _REGISTRY.inc("planner.compile.misses")
            _REGISTRY.inc("planner.compile.time_s", plan_time_s)
            return True
        self.hits += 1
        _REGISTRY.inc("planner.compile.hits")
        entry["calls"] += 1
        return False

    def stats(self) -> Dict[str, float]:
        return {
            "calls": self.calls,
            "hits": self.hits,
            "misses": self.misses,
            "compile_time_s": self.compile_time_s,
            "distinct_signatures": len(self.signatures),
        }


COMPILE_CACHE = PlannerCompileCache()


def compile_cache_stats() -> Dict[str, float]:
    """Snapshot of the planner compile cache (counts since the last
    ``reset_compile_cache_counters`` call; ``distinct_signatures`` is
    process-lifetime)."""
    return COMPILE_CACHE.stats()


def reset_compile_cache_counters() -> None:
    """Zero the windowed hit/miss/compile-time counters (the signature
    registry — what decides hit vs miss — is kept: compiled XLA programs
    don't vanish on reset)."""
    COMPILE_CACHE.reset_counters()


def plans_from_arrays(
    low: LoweredProblem,
    notes: Sequence[str],
    placed_b: np.ndarray,   # [B, S] bool (already sliced to real S)
    fcur_b: np.ndarray,     # [B, S]
    ncur_b: np.ndarray,     # [B, S]
    skipped_b: np.ndarray,  # [B, S] bool
    infeas_b: np.ndarray,   # [B] bool
    fail_b: np.ndarray,     # [B] int — first mandatory failure, -1 if none
    order_b: np.ndarray,    # [B, S] greedy construction order
    em_b: np.ndarray,       # [B] emissions (grams)
) -> List[DeploymentPlan]:
    """Materialize one :class:`DeploymentPlan` per branch row from sliced
    planner output arrays — the shared object-construction tail of
    ``GreenScheduler.plan`` and the fleet planner's ``plan_many`` (both
    must build byte-identical plan objects from identical arrays for the
    fleet-vs-sequential parity guarantee to be checkable at the plan
    level)."""
    S = low.S
    plans: List[DeploymentPlan] = []
    for b in range(placed_b.shape[0]):
        if infeas_b[b]:
            sid = low.service_ids[int(fail_b[b])]
            plans.append(DeploymentPlan(
                placements=(),
                feasible=False,
                notes=tuple(notes) + (f"no feasible node for {sid}",),
            ))
            continue
        assign = {
            low.service_ids[s]: (
                low.flavour_names[s][int(fcur_b[b, s])],
                low.node_ids[int(ncur_b[b, s])])
            for s in range(S) if placed_b[b, s]
        }
        plans.append(DeploymentPlan(
            placements=tuple(
                Placement(sid, f, n)
                for sid, (f, n) in sorted(assign.items())),
            skipped_services=tuple(
                low.service_ids[int(s)] for s in order_b[b]
                if skipped_b[b, s]),
            total_emissions_g=float(em_b[b]),
            feasible=True,
            notes=tuple(notes),
        ))
    return plans


def _pad1(a: np.ndarray, size: int) -> np.ndarray:
    """Pad a 1-D array with zeros (False / 0) up to ``size``."""
    if a.shape[0] == size:
        return a
    out = np.zeros(size, dtype=a.dtype)
    out[:a.shape[0]] = a
    return out


def _static_feasibility(low: LoweredProblem) -> np.ndarray:
    """Load-independent feasibility mask [S, F, N]: real flavour slot,
    subnet compatibility, availability."""
    return (low.valid[:, :, None]
            & low.compat[:, None, :]
            & (low.avail_cap[None, None, :] >= low.avail_req[:, :, None]))


def _warm_start_state(
    low: LoweredProblem,
    stat_feas: np.ndarray,
    initial: Mapping[str, Tuple[str, str]],
) -> Tuple[Optional[Tuple], Optional[str]]:
    """Validate an initial assignment against the lowered masks.

    Returns ``((placed, fcur, ncur, cpu_load, ram_load), None)`` when every
    entry names a known (service, flavour, node), passes the static
    feasibility mask, and the accumulated loads respect node capacities;
    otherwise ``(None, reason)`` so the caller can reject-and-rebuild.
    """
    S, N = low.S, low.N
    sidx, nidx = low.service_index(), low.node_index()
    placed = np.zeros(S, dtype=bool)
    fcur = np.zeros(S, dtype=np.int64)
    ncur = np.zeros(S, dtype=np.int64)
    cpu_load = np.zeros(N)
    ram_load = np.zeros(N)
    for sid, (fname, nid) in initial.items():
        s, n = sidx.get(sid), nidx.get(nid)
        if s is None or n is None:
            return None, f"unknown service/node {sid!r} -> {nid!r}"
        try:
            f = low.flavour_names[s].index(fname)
        except ValueError:
            return None, f"unknown flavour {fname!r} of {sid!r}"
        if not stat_feas[s, f, n]:
            return None, f"{sid!r} infeasible on {nid!r} (mask)"
        placed[s] = True
        fcur[s], ncur[s] = f, n
        cpu_load[n] += low.cpu_req[s, f]
        ram_load[n] += low.ram_req[s, f]
    if (cpu_load > low.cpu_cap).any() or (ram_load > low.ram_cap).any():
        return None, "capacity exceeded"
    return (placed, fcur, ncur, cpu_load, ram_load), None


@dataclass
class GreenScheduler:
    """Array-native greedy + vectorized best-improvement local search.

    One public entrypoint: ``plan(problem: PlacementProblem)`` returns a
    :class:`~repro.core.problem.PlanResult` with one plan per scenario
    branch (B=1 when the problem carries no scenario batch).  The problem
    object bundles everything the planner needs — lowering (dense or
    sparse communication backend), constraints, optional what-if
    scenarios, optional warm start.
    """

    config: SchedulerConfig = field(default_factory=SchedulerConfig)

    def plan(self, problem: PlacementProblem) -> PlanResult:
        """Plan a deployment: ``plan(problem) -> PlanResult``.

        Scenarios and warm start travel on the problem
        (``problem.with_scenarios(...)`` / ``problem.with_warm_start(...)``).
        A warm start maps service -> (flavour, node); it is verified
        against the capacity / subnet / availability masks first, rejected
        as a whole on any violation, and the plan rebuilt greedily from
        scratch (noted on the returned plan).
        """
        if not isinstance(problem, PlacementProblem):
            raise TypeError(
                "GreenScheduler.plan takes a PlacementProblem; the old "
                "positional plan(app, infra, computation, communication, "
                "...) and plan_batch forms were removed — build a problem "
                "with PlacementProblem.build(...) or pipeline."
                "problem_for(out) instead")
        return self._plan_problem(problem)

    # -- the one real planning path ----------------------------------------

    def _plan_problem(self, problem: PlacementProblem) -> PlanResult:
        cfg = self.config
        low = problem.lowering
        constraints = problem.constraints if cfg.use_green_constraints \
            else ()
        scenarios = problem.scenarios
        if scenarios is None:
            scenarios = ScenarioBatch(
                ci=np.asarray(low.ci, dtype=float)[None, :])
        S, N = low.S, low.N
        B = scenarios.B

        notes: List[str] = []
        warm = None
        stat_feas_real = None
        initial = problem.initial_assignment
        if initial is not None:
            stat_feas_real = _static_feasibility(low)
            warm, err = _warm_start_state(low, stat_feas_real, initial)
            if warm is None:
                notes.append(
                    f"warm start rejected ({err}); rebuilt from scratch")
        if warm is None:
            warm = (np.zeros(S, dtype=bool), np.zeros(S, dtype=np.int64),
                    np.zeros(S, dtype=np.int64), np.zeros(N), np.zeros(N))

        if S == 0 or N == 0:
            return self._degenerate_result(problem, low, scenarios, notes)
        ci_b, E_b, order_b = scenarios.materialize(low)
        # the pairwise-transmission mean CI, per branch, over REAL nodes
        # (the planner takes it explicitly so bucket padding can't skew it)
        ci_mean_b = np.asarray(ci_b, dtype=float).mean(axis=1)

        # -- shape bucketing: round (S, F, N, L, B) up to the configured
        # bucket boundaries and pad with masked-out phantom entries so one
        # compiled program serves every shape in the bucket; results are
        # sliced back to the real [B, :S] below.
        F = low.F
        L = low.comm.n_links if low.comm.kind == "sparse" else None
        shape = (B, S, F, N, L)
        plow, bucketed = low, False
        if cfg.bucket is not None:
            S_p, F_p, N_p, L_p, B_p = cfg.bucket.pad_dims(S, F, N, L, B)
            bucketed = (S_p, F_p, N_p, L_p, B_p) != (S, F, N, L, B)
            plow = pad_lowering(low, S_p, F_p, N_p, L_p)
            if B_p > B:
                # phantom branches replay branch 0; sliced away afterwards
                rep = np.repeat(ci_b[:1], B_p - B, axis=0)
                ci_b = np.concatenate([ci_b, rep], axis=0)
                ci_mean_b = np.concatenate(
                    [ci_mean_b, np.repeat(ci_mean_b[:1], B_p - B)])
                E_b = np.concatenate(
                    [E_b, np.repeat(E_b[:1], B_p - B, axis=0)], axis=0)
                order_b = np.concatenate(
                    [order_b, np.repeat(order_b[:1], B_p - B, axis=0)],
                    axis=0)
            if N_p > N:
                ci_b = np.concatenate(
                    [ci_b, np.zeros((ci_b.shape[0], N_p - N))], axis=1)
            if S_p > S or F_p > F:
                E_pad = np.zeros((E_b.shape[0], S_p, F_p))
                E_pad[:, :S, :F] = E_b
                E_b = E_pad
                # phantom services go LAST in every branch's greedy order
                order_b = np.concatenate([
                    order_b,
                    np.broadcast_to(
                        np.arange(S, S_p, dtype=order_b.dtype),
                        (order_b.shape[0], S_p - S))], axis=1)
            warm = (
                _pad1(warm[0], S_p), _pad1(warm[1], S_p),
                _pad1(warm[2], S_p), _pad1(warm[3], N_p),
                _pad1(warm[4], N_p))
        padded_shape = (ci_b.shape[0], plow.S, plow.F, plow.N,
                        plow.comm.n_links if plow.comm.kind == "sparse"
                        else None)

        P, A = lower_constraints(plow, constraints)
        # reuse the warm-start validation mask when the lowering wasn't
        # padded (the mask is O(S*F*N) — twice per tick would be real)
        stat_feas = stat_feas_real if (plow is low
                                       and stat_feas_real is not None) \
            else _static_feasibility(plow)

        import jax

        sig = (plow.comm.kind,) + padded_shape
        planner = _batched_planner(sig)
        # x64 keeps branch plans bit-comparable across batch sizes and
        # backends: a float32 downcast would drown the _EPS improvement
        # threshold in rounding noise and let the local search ping-pong
        # on near-ties.
        bufs = _pack_plan_args(sig, (
            ci_b, ci_mean_b, E_b, order_b, *warm,
            *plow.comm.planner_args(), P, A, stat_feas,
            plow.cpu_req, plow.ram_req, plow.cpu_cap, plow.ram_cap,
            plow.must, plow.cost,
            cfg.money_weight, cfg.pref_weight, cfg.emission_weight,
            cfg.green_penalty,
            cfg.local_search_rounds * max(1, S)))
        t_dispatch = time.perf_counter()
        with jax.enable_x64(True):
            out = planner(*bufs)
        t_wait = time.perf_counter()
        out = jax.block_until_ready(out)
        t_fetch = time.perf_counter()
        out = np.asarray(out)
        t_decode = time.perf_counter()
        # phantom branches and services sliced away
        placed_b, fcur_b, ncur_b, skipped_b, infeas_b, fail_b = \
            _unpack_plan_out(out, B, S, plow.S, order_b.dtype)
        plan_time_s = t_decode - t_dispatch
        compiled = COMPILE_CACHE.record(sig, plan_time_s)
        cc = COMPILE_CACHE
        stats = PlanStats(
            backend=plow.comm.kind, shape=shape, padded_shape=padded_shape,
            signature=sig, bucketed=bucketed, compiled=compiled,
            compile_time_s=plan_time_s if compiled else 0.0,
            plan_time_s=plan_time_s, cache_hits=cc.hits,
            cache_misses=cc.misses, t_dispatch=t_dispatch, t_wait=t_wait,
            t_fetch=t_fetch, t_decode=t_decode, args=len(bufs),
            h2d_bytes=sum(b.nbytes for b in bufs), outs=1,
            d2h_bytes=out.nbytes)
        ci_b = ci_b[:B, :N]
        E_b = E_b[:B, :S, :F]
        order_b = order_b[:B, :S]
        em_b = batched_lowered_emissions(
            low, placed_b, fcur_b, ncur_b, ci=ci_b,
            E=E_b if scenarios.E is not None else None)

        plans = plans_from_arrays(
            low, notes, placed_b, fcur_b, ncur_b, skipped_b, infeas_b,
            fail_b, order_b, em_b)
        feas_mask = np.array([p.feasible for p in plans])
        return PlanResult(
            problem=problem, plans=plans, placed=placed_b, fcur=fcur_b,
            ncur=ncur_b,
            emissions_g=np.where(feas_mask, em_b, np.inf),
            stats=stats)

    def _degenerate_result(self, problem, low, scenarios, notes) -> PlanResult:
        """Host-side path for shape-degenerate problems (no services or no
        nodes) — mirrors the greedy semantics with an empty candidate set:
        optional services are skipped in construction order, the first
        mandatory service makes the whole plan infeasible."""
        skipped: List[str] = []
        fail_sid: Optional[str] = None
        if low.N == 0:
            for s in map(int, low.order):
                if low.must[s]:
                    fail_sid = low.service_ids[s]
                    break
                skipped.append(low.service_ids[s])
        if fail_sid is not None:
            plan = DeploymentPlan(
                placements=(), feasible=False,
                notes=tuple(notes) + (f"no feasible node for {fail_sid}",))
        else:
            plan = DeploymentPlan(
                placements=(), skipped_services=tuple(skipped),
                total_emissions_g=0.0, feasible=True, notes=tuple(notes))
        B, S = scenarios.B, low.S
        return PlanResult(
            problem=problem, plans=[plan] * B,
            placed=np.zeros((B, S), dtype=bool),
            fcur=np.zeros((B, S), dtype=np.int64),
            ncur=np.zeros((B, S), dtype=np.int64),
            emissions_g=np.zeros(B) if plan.feasible
            else np.full(B, np.inf))


# ---------------------------------------------------------------------------
# Legacy reference implementation (object-walking), kept for equivalence
# testing and old-vs-new benchmarking.
# ---------------------------------------------------------------------------


def _constraint_maps(
    constraints: Sequence[Constraint],
) -> Tuple[Dict[Tuple[str, str, str], float], Dict[Tuple[str, str], float]]:
    avoid: Dict[Tuple[str, str, str], float] = {}
    affinity: Dict[Tuple[str, str], float] = {}
    for c in constraints:
        if isinstance(c, AvoidNode):
            avoid[(c.service, c.flavour, c.node)] = c.weight * c.memory_weight
        elif isinstance(c, Affinity):
            affinity[(c.service, c.other)] = c.weight * c.memory_weight
    return avoid, affinity


def _flavour_energy(
    svc: Service, fname: str, computation: Mapping[Tuple[str, str], float]
) -> float:
    v = computation.get((svc.component_id, fname))
    if v is not None:
        return v
    e = svc.flavour(fname).energy_kwh
    return e if e is not None else 0.0


def reference_objective(
    app: Application,
    infra: Infrastructure,
    computation: Mapping[Tuple[str, str], float],
    communication: Mapping[Tuple[str, str, str], float],
    constraints: Sequence[Constraint],
    config: SchedulerConfig,
    assign: Mapping[str, Tuple[str, str]],
) -> float:
    """The legacy object-walking objective J(assign) — ground truth for
    equivalence tests of the array-native scheduler."""
    cfg = config
    if not cfg.use_green_constraints:
        constraints = ()
    avoid, affinity = _constraint_maps(constraints)
    mean_ci = _mean_ci(infra)
    money = pref = emissions = green = 0.0
    for sid, (fname, nid) in assign.items():
        svc = app.service(sid)
        node = infra.node(nid)
        req = svc.flavour(fname).requirements
        money += node.cost_per_cpu_hour * req.cpu
        pref += svc.flavours_order.index(fname)
        if cfg.emission_weight:
            ci = node.carbon if node.carbon is not None else mean_ci
            emissions += _flavour_energy(svc, fname, computation) * ci
        g = avoid.get((sid, fname, nid))
        if g:
            green += g
    for (s, f, z), e in communication.items():
        if s in assign and z in assign and assign[s][0] == f:
            if assign[s][1] != assign[z][1]:
                if cfg.emission_weight:
                    emissions += e * mean_ci
                g = affinity.get((s, z))
                if g:
                    green += g
    return (cfg.money_weight * money
            + cfg.pref_weight * pref
            + cfg.emission_weight * emissions
            + cfg.green_penalty * green)


@dataclass
class ReferenceScheduler:
    """The original pure-Python scheduler: greedy construction with full
    objective recomputation per candidate + first-improvement local search.
    O(S^2*F*N*(S+L)) per greedy pass — retained as the correctness and
    performance reference for ``GreenScheduler``."""

    config: SchedulerConfig = field(default_factory=SchedulerConfig)

    def plan(
        self,
        app: Application,
        infra: Infrastructure,
        computation: Mapping[Tuple[str, str], float],
        communication: Mapping[Tuple[str, str, str], float],
        constraints: Sequence[Constraint] = (),
    ) -> DeploymentPlan:
        cfg = self.config
        if not cfg.use_green_constraints:
            constraints = ()
        nodes = list(infra.nodes)

        def objective(assign: Dict[str, Tuple[str, str]]) -> float:
            return reference_objective(
                app, infra, computation, communication, constraints, cfg,
                assign)

        def feasible(svc: Service, fname: str, nid: str,
                     load: Dict[str, Tuple[float, float]]) -> bool:
            node = infra.node(nid)
            if not subnet_compatible(svc, node):
                return False
            req = svc.flavour(fname).requirements
            used_cpu, used_ram = load.get(nid, (0.0, 0.0))
            if used_cpu + req.cpu > node.capabilities.cpu:
                return False
            if used_ram + req.ram_gb > node.capabilities.ram_gb:
                return False
            if node.capabilities.availability < req.availability:
                return False
            return True

        # --- greedy construction: heaviest services first, best (flavour,
        # node) by the objective; flavoursOrder breaks ties.
        order = sorted(
            app.services,
            key=lambda s: -max(
                (_flavour_energy(s, f.name, computation)
                 for f in s.flavours), default=0.0
            ),
        )
        assign: Dict[str, Tuple[str, str]] = {}
        load: Dict[str, Tuple[float, float]] = {}
        skipped: List[str] = []
        for svc in order:
            best: Optional[Tuple[float, int, int, str, str]] = None
            for pref_rank, fname in enumerate(svc.flavours_order):
                for k, node in enumerate(nodes):
                    if not feasible(svc, fname, node.node_id, load):
                        continue
                    trial = dict(assign)
                    trial[svc.component_id] = (fname, node.node_id)
                    cand = (objective(trial), pref_rank, k, fname,
                            node.node_id)
                    if best is None or cand < best:
                        best = cand
            if best is None:
                if svc.must_deploy:
                    return DeploymentPlan(
                        placements=(),
                        feasible=False,
                        notes=(f"no feasible node for {svc.component_id}",),
                    )
                skipped.append(svc.component_id)
                continue
            _, _, _, fname, nid = best
            assign[svc.component_id] = (fname, nid)
            req = svc.flavour(fname).requirements
            cpu, ram = load.get(nid, (0.0, 0.0))
            load[nid] = (cpu + req.cpu, ram + req.ram_gb)

        # --- first-improvement local search over single relocations.
        for _ in range(cfg.local_search_rounds):
            improved = False
            base = objective(assign)
            for sid in list(assign):
                svc = app.service(sid)
                cur = assign[sid]
                for fname in svc.flavours_order:
                    for node in nodes:
                        if (fname, node.node_id) == cur:
                            continue
                        load2 = _load_without(app, assign, sid)
                        if not feasible(svc, fname, node.node_id, load2):
                            continue
                        trial = dict(assign)
                        trial[sid] = (fname, node.node_id)
                        c = objective(trial)
                        if c + _EPS < base:
                            assign, base, improved = trial, c, True
            if not improved:
                break

        placements = tuple(
            Placement(sid, f, n) for sid, (f, n) in sorted(assign.items())
        )
        return DeploymentPlan(
            placements=placements,
            skipped_services=tuple(skipped),
            total_emissions_g=plan_emissions(
                app, infra, assign, computation, communication
            ),
            feasible=True,
        )


def _mean_ci(infra: Infrastructure) -> float:
    cis = [n.carbon for n in infra.nodes if n.carbon is not None]
    return sum(cis) / len(cis) if cis else 0.0


def _load_without(
    app: Application, assign: Dict[str, Tuple[str, str]], skip: str
) -> Dict[str, Tuple[float, float]]:
    load: Dict[str, Tuple[float, float]] = {}
    for sid, (fname, nid) in assign.items():
        if sid == skip:
            continue
        req = app.service(sid).flavour(fname).requirements
        cpu, ram = load.get(nid, (0.0, 0.0))
        load[nid] = (cpu + req.cpu, ram + req.ram_gb)
    return load


def plan_emissions(
    app: Application,
    infra: Infrastructure,
    assign: Dict[str, Tuple[str, str]],
    computation: Mapping[Tuple[str, str], float],
    communication: Mapping[Tuple[str, str, str], float],
) -> float:
    """True emissions (g) of a plan: computation + inter-node transmission."""
    mean_ci = _mean_ci(infra)
    total = 0.0
    for sid, (fname, nid) in assign.items():
        node = infra.node(nid)
        ci = node.carbon if node.carbon is not None else mean_ci
        e = computation.get((sid, fname))
        if e is None:
            fe = app.service(sid).flavour(fname).energy_kwh
            e = fe if fe is not None else 0.0
        total += e * ci
    for (s, f, z), e in communication.items():
        if s in assign and z in assign and assign[s][0] == f:
            if assign[s][1] != assign[z][1]:
                total += e * mean_ci
    return total


def plan_cost(app: Application, infra: Infrastructure,
              assign: Dict[str, Tuple[str, str]]) -> float:
    return sum(
        infra.node(nid).cost_per_cpu_hour
        * app.service(sid).flavour(fname).requirements.cpu
        for sid, (fname, nid) in assign.items()
    )
