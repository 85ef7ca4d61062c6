"""The planner call's packed boundary: three buffers in, one array out.

``GreenScheduler.plan`` lays the planner's arguments out in a float64, an
int64 and a bool buffer and gets one int32 array back.  The packed call
must give, bit for bit and dtype for dtype, what the plain
``jit(vmap(planner_single))`` gives on the unpacked arguments: dense and
sparse, one branch and four, exact and bucketed shapes, a warm start
accepted and one rejected.
"""
import jax
import numpy as np
import pytest

from benchmarks.scheduler_scalability import synth
from repro.core import scheduler
from repro.core.lowering import ScenarioBatch
from repro.core.problem import BucketSpec, PlacementProblem
from repro.core.scheduler import GreenScheduler, SchedulerConfig

S, N = 11, 6
BUCKET = BucketSpec.grid(s=(16,), f=(4,), n=(8,), b=(8,), l=(64,))


def _problem(backend, B, warm):
    problem = PlacementProblem.build(*synth(S, N, seed=5), backend=backend)
    if B > 1:
        low, rng = problem.lowering, np.random.default_rng(B)
        problem = problem.with_scenarios(ScenarioBatch(
            ci=low.ci[None] * rng.uniform(0.5, 1.5, (B, low.N)),
            E=low.E[None] * rng.uniform(0.5, 1.5, (B,) + low.E.shape)))
    if warm == "accepted":
        first = GreenScheduler(SchedulerConfig()).plan(problem).plans[0]
        problem = problem.with_warm_start(
            {p.service: (p.flavour, p.node) for p in first.placements})
    else:
        problem = problem.with_warm_start({"s0": ("f0", "nowhere")})
    return problem


@pytest.mark.parametrize("warm", ["accepted", "rejected"])
@pytest.mark.parametrize("bucketed", [False, True])
@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_packed_call_equals_the_plain_planner(monkeypatch, backend, B,
                                              bucketed, warm):
    problem = _problem(backend, B, warm)
    captured = []
    pack = scheduler._pack_plan_args

    def spy(sig, args):
        captured.append((sig, args))
        return pack(sig, args)

    monkeypatch.setattr(scheduler, "_pack_plan_args", spy)
    cfg = SchedulerConfig(emission_weight=0.7,
                          bucket=BUCKET if bucketed else None)
    res = GreenScheduler(cfg).plan(problem)
    ((sig, args),) = captured
    kind, B_p, S_p = sig[:3]
    assert (kind, res.stats.bucketed) == (backend, bucketed)
    rejected = any("warm start rejected" in n for n in res.plans[0].notes)
    assert rejected == (warm == "rejected")
    assert bool(np.asarray(args[4]).any()) == (warm == "accepted")

    plain = jax.jit(jax.vmap(scheduler.planner_single(kind),
                             in_axes=(0,) * 4 + (None,) * (len(args) - 4)))
    bufs = pack(sig, args)
    assert [b.dtype for b in bufs] == [np.float64, np.int64, np.bool_]
    with jax.enable_x64(True):
        want = [np.asarray(a) for a in plain(*args)]
        out = np.asarray(scheduler._batched_planner(sig)(*bufs))
    assert out.dtype == np.int32 and out.shape == (B_p, 4 * S_p + 2)
    got = scheduler._unpack_plan_out(out, B_p, S_p, S_p,
                                     np.asarray(args[3]).dtype)
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    # what the caller sees is the plain planner's, phantoms sliced away
    placed, fcur, ncur = (w[:B, :S] for w in want[:3])
    assert np.array_equal(res.placed, placed)
    assert np.array_equal(res.fcur, fcur) and res.fcur.dtype == fcur.dtype
    assert np.array_equal(res.ncur, ncur) and res.ncur.dtype == ncur.dtype
    assert res.stats.args == 3 and res.stats.outs == 1
    assert res.stats.h2d_bytes == sum(b.nbytes for b in bufs)
    assert res.stats.d2h_bytes == out.nbytes


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_layout_tiles_each_buffer(backend):
    sig = (backend, 4, 16, 4, 8, 64 if backend == "sparse" else None)
    slots, sizes = scheduler._plan_layout(sig)
    assert len(slots) == 9 + scheduler.PLANNER_COMM_ARGC[backend] + 14
    for buf in range(3):
        spans = [(off, int(np.prod(shape))) for b, off, shape in slots
                 if b == buf]
        ends = [0] + [off + n for off, n in spans]
        assert [off for off, _ in spans] == ends[:-1]
        assert ends[-1] == sizes[buf]


def test_packing_refuses_a_wrong_shape_or_a_float_in_the_integers():
    problem = PlacementProblem.build(*synth(S, N, seed=5))
    captured = []
    pack = scheduler._pack_plan_args

    def spy(sig, args):
        captured.append((sig, args))
        return pack(sig, args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scheduler, "_pack_plan_args", spy)
        GreenScheduler(SchedulerConfig()).plan(problem)
    ((sig, args),) = captured
    bad_shape = list(args)
    bad_shape[0] = np.asarray(args[0])[:, :-1]
    with pytest.raises(ValueError, match="layout"):
        pack(sig, bad_shape)
    bad_dtype = list(args)
    bad_dtype[3] = np.asarray(args[3], dtype=float) + 0.5   # the order
    with pytest.raises(TypeError):
        pack(sig, bad_dtype)
