"""Scheduler scalability: legacy object-walking vs array-native core.

Sweeps (S services, N nodes) and times one full ``plan(problem)`` call
(greedy + local search) for the retained ``ReferenceScheduler`` and the
unified ``GreenScheduler`` on the same synthetic problem and the same
config.  GreenScheduler timings EXCLUDE the one-time XLA compile (one
warmup call per shape): the adaptive loop replans the same shapes every
tick, so steady-state cost is what the trajectory tracks.

Beyond the shared sweep, a sparse-backend frontier section plans an
S=2000, N=200 problem through ``SparseCommLowering`` — a scale where the
dense ``[S, F, S]`` communication tensors and the O(S^2*F*N) move-grid
einsum are reported infeasible to materialize by the auto-selection
policy (``SPARSE_AUTO_THRESHOLD``), and records what the dense backend
WOULD have allocated.

A compile-cache section (run FIRST, against a cold planner cache) plans a
mixed-shape sweep through the shape-bucketed planner (``BucketSpec`` grid
with the acceptance point (200, 100) as a boundary): every shape rounds
up to one bucket, so >= 8 shapes must cost >= 4x fewer XLA compiles than
shapes, and bucketed steady-state plan time at the boundary must stay
within 1.25x of the exact-shape time.

Writes ``BENCH_scheduler.json`` so the perf trajectory is tracked
PR-over-PR; asserts the array-native plan's objective never exceeds the
legacy plan's, that dense and sparse backends agree at a shared point,
and that the speedup at (S=200, N=100) is at least 10x.

CI runs ``--smoke --check BENCH_scheduler.json``: a small sweep whose
measured speedup must stay within --tolerance (default 20%) of the
committed baseline's at the same point, plus the compile-cache hit-rate
gate over the mixed-shape smoke sweep.  Set
``JAX_COMPILATION_CACHE_DIR`` to persist compiled programs across runs
(CI caches it so cold compiles are paid once per toolchain bump).

  PYTHONPATH=src python -m benchmarks.scheduler_scalability [--smoke]
      [--check BENCH_scheduler.json] [--tolerance 0.2]
"""
import argparse
import json
import random
import sys
import time

from repro.core.lowering import SPARSE_AUTO_THRESHOLD, lower
from repro.core.problem import BucketSpec, PlacementProblem
from repro.core.scheduler import (
    GreenScheduler,
    ReferenceScheduler,
    SchedulerConfig,
    reference_objective,
)
from repro.jax_cache import enable_persistent_cache
from repro.obs import metrics_scope
from repro.core.types import (
    Affinity,
    Application,
    AvoidNode,
    Flavour,
    FlavourRequirements,
    Infrastructure,
    Node,
    NodeCapabilities,
    Service,
)

OUT_JSON = "BENCH_scheduler.json"
REQUIRED_SPEEDUP = 10.0          # acceptance floor at (200, 100)
# Absolute speedup a healthy host shows at the smoke point, regardless of
# hardware (measured ~1000-2000x on dev machines): the relative >20%
# check below tracks PR-over-PR drift on comparable hosts, but a pure
# ratio of interpreter time to XLA time does not transfer across CPU
# generations — a host that still clears this floor is not failed on the
# relative check alone.
SMOKE_SPEEDUP_FLOOR = 200.0
FLAVOURS = 2

# Bucket boundaries for the compile-cache sweep: an explicit grid tuned
# to the sweep envelope (the acceptance point (200, 100) is a boundary,
# so bucketed planning there pays no padding overhead).
BUCKET_GRID = BucketSpec.grid(
    s=(25, 50, 100, 200, 400, 800, 1600),
    f=(2, 4),
    n=(25, 50, 100, 200, 400),
    b=(1, 2, 4, 8, 16),
)
# Mixed shapes that all round up to the (200, 100) bucket (full mode) /
# the (100, 50) bucket (smoke): >= 4x fewer XLA compiles than shapes.
CACHE_SWEEP = ((110, 60), (120, 70), (130, 80), (140, 90),
               (150, 100), (160, 60), (180, 80), (200, 100))
CACHE_SWEEP_SMOKE = ((60, 30), (70, 35), (80, 40), (100, 50))
# Bucketed steady-state time at the acceptance point must stay within
# this factor of the exact-shape time (the point IS a bucket boundary).
BUCKET_OVERHEAD_CEILING = 1.25


def synth(n_services: int, n_nodes: int, seed: int = 0,
          flavours: int = FLAVOURS):
    """A dense-ish placement problem: F flavours per service, ring links,
    AvoidNode/Affinity soft constraints."""
    rnd = random.Random(seed)
    services = tuple(
        Service(f"s{i}", flavours=tuple(
            Flavour(f"f{k}", requirements=FlavourRequirements(
                cpu=rnd.choice([0.5, 1.0, 2.0]),
                ram_gb=rnd.choice([1.0, 2.0, 4.0])))
            for k in range(flavours)))
        for i in range(n_services)
    )
    nodes = tuple(
        Node(f"n{j}", carbon=rnd.uniform(10.0, 600.0),
             cost_per_cpu_hour=rnd.uniform(0.0, 2.0),
             capabilities=NodeCapabilities(
                 cpu=rnd.choice([8.0, 16.0]), ram_gb=64.0))
        for j in range(n_nodes)
    )
    comp = {
        (f"s{i}", f"f{k}"): rnd.uniform(1.0, 100.0)
        for i in range(n_services) for k in range(flavours)
    }
    comm = {
        (f"s{i}", "f0", f"s{(i + 1) % n_services}"): rnd.uniform(0.1, 20.0)
        for i in range(n_services)
    }
    cs = []
    for i in range(0, n_services, 3):
        cs.append(AvoidNode(service=f"s{i}", flavour="f0",
                            node=f"n{rnd.randrange(n_nodes)}",
                            weight=rnd.uniform(0.2, 1.0)))
    for i in range(0, n_services, 5):
        cs.append(Affinity(service=f"s{i}",
                           other=f"s{(i + 1) % n_services}",
                           weight=rnd.uniform(0.2, 1.0)))
    return (Application("synth", services), Infrastructure("synth", nodes),
            comp, comm, cs)


def _objective(plan, app, infra, comp, comm, cs, cfg):
    assign = {p.service: (p.flavour, p.node) for p in plan.placements}
    return reference_objective(app, infra, comp, comm, cs, cfg, assign)


def _timed_plan(cfg, problem, repeats: int = 1):
    """Steady-state plan wall time (best of ``repeats``): one warmup call
    compiles the shape first."""
    sched = GreenScheduler(cfg)
    sched.plan(problem)
    best, result = None, None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        result = sched.plan(problem)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, result.plan


def compile_cache_sweep(report, shapes, rounds: int, repeats: int,
                        overhead_point=None):
    """Plan a mixed-shape sweep through the shape-bucketed planner cache.

    Every shape in ``shapes`` rounds up to ONE bucket of
    :data:`BUCKET_GRID`, so the whole sweep should trigger at most one
    XLA compile (asserted at >= 4x fewer compiles than shapes — the CI
    hit-rate gate).  When ``overhead_point`` is given (a bucket-boundary
    shape), also measures bucketed vs exact-shape steady-state plan time
    there and asserts the ratio stays under
    :data:`BUCKET_OVERHEAD_CEILING`.  MUST run before anything else
    compiles planner programs, or the compile count is understated.
    """
    cfg = SchedulerConfig.green()
    cfg.local_search_rounds = rounds
    cfg.bucket = BUCKET_GRID
    sched = GreenScheduler(cfg)
    rows = []
    report("\n# Compile cache: mixed shapes, one bucket, one XLA program")
    report(f"{'S':>5} {'N':>5} {'bucket':>12} {'compiled':>9} "
           f"{'t_plan_s':>9}")
    # metrics_scope reads DELTAS of the process-global registry — no
    # reset needed, so this sweep no longer clobbers counters other
    # benchmarks (or an embedding process) may be reading
    with metrics_scope() as scope:
        for S, N in shapes:
            app, infra, comp, comm, cs = synth(S, N)
            problem = PlacementProblem.build(app, infra, comp, comm, cs)
            t0 = time.perf_counter()
            result = sched.plan(problem)
            dt = time.perf_counter() - t0
            assert result.plan.feasible
            st = result.stats
            rows.append({"S": S, "N": N,
                         "bucket": list(st.padded_shape[1:4]),
                         "compiled": st.compiled, "t_plan_s": dt})
            report(f"{S:>5} {N:>5} {str(st.padded_shape[1:4]):>12} "
                   f"{str(st.compiled):>9} {dt:>9.3f}")
    compiles = int(scope.delta("planner.compile.misses"))
    hits = int(scope.delta("planner.compile.hits"))
    compile_time_s = scope.delta("planner.compile.time_s")
    expected_hits = len(shapes) - max(1, len(shapes) // 4)
    report(f"# {len(shapes)} shapes -> {compiles} XLA compile(s), "
           f"{hits} cache hits ({compile_time_s:.1f}s compiling)")
    assert compiles * 4 <= len(shapes), (
        f"compile-cache gate: {compiles} compiles for {len(shapes)} "
        f"shapes (need >= 4x fewer)")
    assert hits >= expected_hits, (hits, expected_hits)

    out = {"bucket_grid": {"s": BUCKET_GRID.s, "f": BUCKET_GRID.f,
                           "n": BUCKET_GRID.n, "b": BUCKET_GRID.b},
           "shapes": len(shapes), "compiles": compiles, "hits": hits,
           "expected_hits": expected_hits,
           "compile_time_s": compile_time_s, "sweep": rows}

    if overhead_point is not None:
        cfg_exact = SchedulerConfig.green()
        cfg_exact.local_search_rounds = rounds
        S, N = overhead_point
        t_exact, t_bucketed = _interleaved_times(
            cfg_exact, cfg, synth(S, N), repeats)
        ratio = t_bucketed / max(t_exact, 1e-9)
        report(f"# bucketed steady-state at ({S}, {N}): "
               f"{t_bucketed*1e3:.1f}ms vs exact {t_exact*1e3:.1f}ms "
               f"-> {ratio:.2f}x (ceiling {BUCKET_OVERHEAD_CEILING}x)")
        assert ratio <= BUCKET_OVERHEAD_CEILING, (t_bucketed, t_exact)
        out["overhead"] = {"S": S, "N": N, "t_exact_s": t_exact,
                           "t_bucketed_s": t_bucketed, "ratio": ratio}
        # interior point: padding overhead when the shape is strictly
        # inside the bucket (informational, not gated — you pay for the
        # bucket you round up to)
        S_i, N_i = shapes[len(shapes) // 2]
        t_exact_i, t_bucket_i = _interleaved_times(
            cfg_exact, cfg, synth(S_i, N_i), repeats)
        out["interior_overhead"] = {
            "S": S_i, "N": N_i, "t_exact_s": t_exact_i,
            "t_bucketed_s": t_bucket_i,
            "ratio": t_bucket_i / max(t_exact_i, 1e-9)}
        report(f"# interior ({S_i}, {N_i}): bucketed "
               f"{t_bucket_i*1e3:.1f}ms vs exact {t_exact_i*1e3:.1f}ms "
               f"(informational)")
    return out


def _interleaved_times(cfg_a, cfg_b, scenario, repeats: int):
    """Best-of-``repeats`` steady-state plan time for two configs on one
    problem, ALTERNATING a/b per round so slow host drift (frequency
    scaling, background load over a long benchmark run) biases neither
    side — the overhead gate compares their ratio."""
    app, infra, comp, comm, cs = scenario
    problem = PlacementProblem.build(app, infra, comp, comm, cs)
    scheds = (GreenScheduler(cfg_a), GreenScheduler(cfg_b))
    for s in scheds:
        s.plan(problem)  # warmup: compile / prime the program cache
    best = [None, None]
    for _ in range(max(repeats, 3)):
        for i, s in enumerate(scheds):
            t0 = time.perf_counter()
            s.plan(problem)
            dt = time.perf_counter() - t0
            best[i] = dt if best[i] is None else min(best[i], dt)
    return best[0], best[1]


def run(report=print, sweep=((50, 25), (100, 50), (200, 100)),
        vec_only_sweep=((500, 200), (1000, 400)),
        sparse_points=((2000, 200),), rounds: int = 2,
        repeats: int = 3, out_json: str = OUT_JSON,
        cache_shapes=CACHE_SWEEP, overhead_point=(200, 100)):
    # the compile-cache sweep must see a cold planner cache: run it first
    cache_out = compile_cache_sweep(report, cache_shapes, rounds, repeats,
                                    overhead_point=overhead_point)
    cfg = SchedulerConfig.green()
    cfg.local_search_rounds = rounds
    rows = []
    report("# Scheduler wall time: legacy (ReferenceScheduler) vs "
           "array-native (GreenScheduler, post-compile)")
    report(f"{'S':>5} {'N':>5} {'t_ref_s':>9} {'t_vec_s':>9} "
           f"{'speedup':>8} {'J_ref':>12} {'J_vec':>12}")
    for S, N in sweep:
        app, infra, comp, comm, cs = synth(S, N)
        t_ref, ref, spent = None, None, 0.0
        for r in range(max(repeats, 1)):
            t0 = time.perf_counter()
            ref = ReferenceScheduler(cfg).plan(app, infra, comp, comm, cs)
            dt = time.perf_counter() - t0
            t_ref = dt if t_ref is None else min(t_ref, dt)
            spent += dt
            # the legacy side is interpreter-bound and fairly stable: cap
            # the CUMULATIVE time spent tightening it, only the fast jit
            # side needs full best-of-N to beat dispatch jitter
            if spent > 60.0:
                break
        problem = PlacementProblem.build(app, infra, comp, comm, cs)
        t_vec, vec = _timed_plan(cfg, problem, repeats=repeats)
        j_ref = _objective(ref, app, infra, comp, comm, cs, cfg)
        j_vec = _objective(vec, app, infra, comp, comm, cs, cfg)
        assert vec.feasible == ref.feasible
        assert j_vec <= j_ref + 1e-9 * max(1.0, abs(j_ref)), \
            (S, N, j_ref, j_vec)
        speedup = t_ref / max(t_vec, 1e-9)
        rows.append({"S": S, "N": N, "t_ref_s": t_ref, "t_vec_s": t_vec,
                     "speedup": speedup, "J_ref": j_ref, "J_vec": j_vec})
        report(f"{S:>5} {N:>5} {t_ref:>9.3f} {t_vec:>9.3f} "
               f"{speedup:>7.1f}x {j_ref:>12.3f} {j_vec:>12.3f}")

    vec_rows = []
    if vec_only_sweep:
        report("\n# Array-native only (legacy intractable at this scale)")
        report(f"{'S':>5} {'N':>5} {'t_vec_s':>9}")
    for S, N in vec_only_sweep:
        app, infra, comp, comm, cs = synth(S, N)
        problem = PlacementProblem.build(app, infra, comp, comm, cs)
        # single-shot: these rows are informational headroom, not gated
        t_vec, plan = _timed_plan(cfg, problem, repeats=1)
        assert plan.feasible
        vec_rows.append({"S": S, "N": N, "t_vec_s": t_vec,
                         "backend": problem.lowering.comm.kind})
        report(f"{S:>5} {N:>5} {t_vec:>9.3f}")

    # dense vs sparse backends must agree where both are materializable
    S, N = sweep[0]
    app, infra, comp, comm, cs = synth(S, N)
    p_d = PlacementProblem.build(app, infra, comp, comm, cs,
                                 backend="dense")
    p_s = PlacementProblem.build(app, infra, comp, comm, cs,
                                 backend="sparse")
    plan_d = GreenScheduler(cfg).plan(p_d).plan
    plan_s = GreenScheduler(cfg).plan(p_s).plan
    j_d = _objective(plan_d, app, infra, comp, comm, cs, cfg)
    j_s = _objective(plan_s, app, infra, comp, comm, cs, cfg)
    assert abs(j_d - j_s) <= 1e-9 * max(1.0, abs(j_d)), (j_d, j_s)
    report(f"\n# backend parity at ({S}, {N}): "
           f"dense J={j_d:.3f} == sparse J={j_s:.3f}")

    sparse_rows = []
    if sparse_points:
        report("\n# Sparse-comm backend (COO edge list; see dense_reported "
               "per row for whether dense was materializable)")
        report(f"{'S':>5} {'N':>5} {'links':>7} {'t_plan_s':>9} "
               f"{'dense_K_GB':>11}")
    for S, N in sparse_points:
        app, infra, comp, comm, cs = synth(S, N)
        dense_elems = S * FLAVOURS * S
        low = lower(app, infra, comp, comm, backend="sparse")
        if dense_elems > SPARSE_AUTO_THRESHOLD:
            auto = lower(app, infra, comp, comm, backend="auto")
            assert auto.comm.kind == "sparse", \
                (S, "auto-selection must pick sparse past the threshold")
        problem = PlacementProblem.build(app, infra, comp, comm, cs,
                                         lowered=low)
        t_plan, plan = _timed_plan(cfg, problem, repeats=1)
        assert plan.feasible
        dense_gb = dense_elems * 17 / 1e9  # K + derived W (f64) + has_link
        if dense_elems > SPARSE_AUTO_THRESHOLD:
            dense_reported = (
                f"infeasible to materialize: S*F*S = {dense_elems:.2e} "
                f"elements per [S,F,S] tensor > auto threshold "
                f"{SPARSE_AUTO_THRESHOLD:.2e} (K/W/has_link x B scenario "
                f"branches, plus the O(S^2*F*N) move-grid einsum)")
        else:
            dense_reported = (
                f"materializable at this size (S*F*S = {dense_elems:.2e} "
                f"<= threshold {SPARSE_AUTO_THRESHOLD:.2e}); point "
                f"exercises the sparse backend only")
        sparse_rows.append({
            "S": S, "N": N, "backend": "sparse",
            "n_links": low.comm.n_links, "t_plan_s": t_plan,
            "dense_K_elements": dense_elems,
            "dense_tensors_gb_est": dense_gb,
            "dense_reported": dense_reported,
        })
        report(f"{S:>5} {N:>5} {low.comm.n_links:>7} {t_plan:>9.3f} "
               f"{dense_gb:>11.2f}")

    top = max(rows, key=lambda r: (r["S"], r["N"]))
    report(f"\n# speedup at S={top['S']}, N={top['N']}: "
           f"{top['speedup']:.1f}x")
    # the 10x acceptance floor is defined at (S=200, N=100); only enforce
    # it when the sweep actually contains that point (quick sweeps don't)
    gate = [r for r in rows if (r["S"], r["N"]) == (200, 100)]
    if gate:
        report(f"# acceptance: {gate[0]['speedup']:.1f}x at (200, 100) "
               f"(floor {REQUIRED_SPEEDUP:.0f}x)")
        assert gate[0]["speedup"] >= REQUIRED_SPEEDUP, gate[0]

    out = {"config": {"local_search_rounds": rounds, "profile": "green",
                      "timing": "post-compile (one warmup per shape)"},
           "old_vs_vectorized": rows, "vectorized_only": vec_rows,
           "sparse_backend": sparse_rows, "compile_cache": cache_out}
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(out, fh, indent=2)
        report(f"# wrote {out_json}")
    return out


def check_regression(out, baseline_path, tolerance=0.2, report=print):
    """Gate: the measured legacy-vs-array-native speedup must stay within
    ``tolerance`` of the committed baseline at every shared sweep point
    (speedup is a ratio of two runs on the SAME host, so it transfers
    across machines far better than absolute wall time)."""
    with open(baseline_path) as fh:
        base = json.load(fh)
    base_rows = {(r["S"], r["N"]): r for r in base.get("old_vs_vectorized",
                                                       [])}
    ok = True
    for r in out["old_vs_vectorized"]:
        b = base_rows.get((r["S"], r["N"]))
        if b is None:
            continue
        # plan quality first: the planner is deterministic, so the
        # objective at a committed sweep point must never regress at all
        j_ok = r["J_vec"] <= b["J_vec"] + 1e-9 * max(1.0, abs(b["J_vec"]))
        ratio = r["speedup"] / max(b["speedup"], 1e-9)
        # perf: >tolerance below the committed baseline AND below the
        # host-independent floor — a slower-but-healthy runner passes
        perf_ok = (ratio >= 1.0 - tolerance
                   or r["speedup"] >= SMOKE_SPEEDUP_FLOOR)
        verdict = "ok" if (j_ok and perf_ok) else "REGRESSED"
        report(f"# check ({r['S']}, {r['N']}): speedup {r['speedup']:.1f}x "
               f"vs baseline {b['speedup']:.1f}x -> {ratio:.2f}, "
               f"J_vec {r['J_vec']:.3f} vs {b['J_vec']:.3f} [{verdict}]")
        ok &= j_ok and perf_ok
    # compile-cache hit rate: hard-gated by the asserts inside
    # compile_cache_sweep (which runs before this on every --smoke /
    # full invocation); reported here so the --check log shows it
    cc = out.get("compile_cache")
    if cc:
        report(f"# compile cache (gated in-sweep): {cc['compiles']} "
               f"compile(s) / {cc['shapes']} shapes, {cc['hits']} hits "
               f"(expect >= {cc['expected_hits']})")
    if ok:
        report(f"# regression gate passed (tolerance {tolerance:.0%})")
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep for CI; does not overwrite the "
                         "tracked BENCH json")
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="fail if speedup regresses vs this committed "
                         "baseline by more than --tolerance")
    ap.add_argument("--tolerance", type=float, default=0.2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_persistent_cache()
    if args.smoke:
        # (100, 50) with best-of-5: at (50, 25) the array-native plan is
        # ~2 ms and dispatch jitter swings the speedup ratio by 2x; at
        # (100, 50) the ~15 ms plan is stable to a few percent while the
        # legacy side still finishes in ~20 s
        out = run(sweep=((100, 50),), vec_only_sweep=(),
                  sparse_points=((600, 100),), repeats=5,
                  out_json=args.out, cache_shapes=CACHE_SWEEP_SMOKE,
                  overhead_point=(100, 50))
    else:
        out = run(out_json=args.out if args.out else OUT_JSON)
    if args.check and not check_regression(out, args.check,
                                           tolerance=args.tolerance):
        sys.exit(1)


if __name__ == "__main__":
    main()
