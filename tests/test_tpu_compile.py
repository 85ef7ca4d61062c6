"""Ahead-of-time compiles of the main path's programs for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached: these tests lower the planner (dense and sparse
backends), the fleet program ``shard_map``-ed over a 4-chip mesh, and the
Pallas kernels with ``interpret=False`` against a described ``v5e:2x2``,
so what the chip's compiler would refuse fails here.  Nothing runs.

Planner and fleet arguments are captured from a real (small, CPU) call
through ``GreenScheduler.plan`` / ``plan_many``, so the compiled program
is the one the entry point builds.  The topology is described inside a
fixture: only the test process that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from benchmarks.scheduler_scalability import synth
from repro.core import scheduler
from repro.core.problem import PlacementProblem
from repro.core.scheduler import GreenScheduler, SchedulerConfig
from repro.fleet import FleetProblem, planner as fleet_planner, plan_many
from repro.kernels.ops import flash_attention, ssd_scan


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spy(monkeypatch, cache, key, build):
    """Record the argument tuples the entry point passes to the jitted
    program cached under ``cache[key]``; returns (program, calls)."""
    program = build()
    calls = []

    def spy(*args):
        calls.append(args)
        return program(*args)

    monkeypatch.setitem(cache, key, spy)
    return program, calls


def _spec(arg, sharding):
    a = np.asarray(arg)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


@pytest.mark.parametrize("backend,S,N", [("dense", 60, 20),
                                         ("sparse", 120, 20)])
def test_planner_compiles_for_v5e(monkeypatch, one_chip, backend, S, N):
    problem = PlacementProblem.build(*synth(S, N), backend=backend)
    build, programs, calls = scheduler._batched_planner, [], []

    def spy(sig):
        program = build(sig)
        programs.append(program)

        def call(*args):
            calls.append(args)
            return program(*args)
        return call

    monkeypatch.setattr(scheduler, "_batched_planner", spy)
    GreenScheduler(SchedulerConfig.green()).plan(problem)
    (program,), (args,) = programs, calls
    # the arguments travel as at most three packed arrays
    assert len(args) <= 3
    assert all(isinstance(a, np.ndarray) for a in args)
    with jax.enable_x64(True):
        compiled = program.lower(
            *(_spec(a, one_chip) for a in args)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # the while-loop local search and the greedy scan stay on the device
    assert "while" in compiled.as_text()


def test_fleet_program_shards_over_four_chips(monkeypatch, topo):
    _, infra, _, _, _ = synth(12, 16, seed=0)
    apps = []
    for i in range(6):
        app, _, comp, comm, cs = synth(12, 16, seed=1 + i)
        apps.append(PlacementProblem.build(app, infra, comp, comm, cs))
    fleet = FleetProblem(apps=tuple(apps))
    program, calls = _spy(
        monkeypatch, fleet_planner._UNCOUPLED_CACHE, "dense",
        lambda: fleet_planner._uncoupled_program("dense"))
    plan_many(fleet, devices=jax.devices()[:1])
    (args,) = calls
    A = np.shape(args[2])[0]
    A4 = -(-A // 4) * 4

    devices = tuple(topo.devices)
    assert len(devices) == 4
    monkeypatch.setattr(fleet_planner, "_SHARDED_CACHE", {})
    sharded = fleet_planner._sharded_program("dense", devices)
    mesh = jax.sharding.Mesh(np.array(devices), ("apps",))
    axes = fleet_planner._app_axes(scheduler.PLANNER_COMM_ARGC["dense"])
    specs = []
    for a, ax in zip(args, axes):
        a = np.asarray(a)
        if ax == 0:   # the app axis, padded up to a multiple of 4
            a = np.zeros((A4,) + a.shape[1:], a.dtype)
        specs.append(_spec(a, NamedSharding(
            mesh, PartitionSpec("apps") if ax == 0 else PartitionSpec())))
    with jax.enable_x64(True):
        compiled = sharded.lower(*specs).compile()
    # each chip holds a quarter of the app axis
    e_shard = compiled.input_shardings[0][2]
    assert e_shard.shard_shape((A4,) + np.shape(args[2])[1:])[0] == A4 // 4


def test_flash_attention_compiles_to_a_tpu_kernel(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_to_a_tpu_kernel(one_chip):
    B, S, nh, hp, n = 1, 2048, 8, 64, 128

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda x, dt, A, b, c: ssd_scan(x, dt, A, b, c, chunk=256,
                                        interpret=False)
    ).lower(spec((B, S, nh, hp)), spec((B, S, nh)), spec((nh,)),
            spec((B, S, n)), spec((B, S, n))).compile()
    assert "tpu_custom_call" in compiled.as_text()
