"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
smoke tests and benchmarks see the real (1-device) platform.

Callers enter a mesh with ``jax.set_mesh(mesh)`` and jit with bare
PartitionSpec shardings, which jax resolves against that ambient mesh.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_from_shape(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Elastic re-mesh entry point (ft.manager.plan_elastic_mesh output)."""
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host offers (tests / examples): (data, model)."""
    n = jax.device_count()
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))
