"""Production meshes. Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Every mesh in the repo is built by :func:`make_mesh` with Auto axes: the
code places arrays with ``NamedSharding``/``with_sharding_constraint``
and lets GSPMD propagate, which Explicit axes (``jax.make_mesh``'s default
since jax 0.7) would reject."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16) ("data","model") = 256 chips (TPU v5e pod).
    Multi-pod: (2,16,16) ("pod","data","model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, data: int = 4, model: int = 2, pod: int = 1):
    """Small mesh for CPU integration tests (requires
    --xla_force_host_platform_device_count >= data*model*pod)."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def dp_axis_names(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_agents_of(mesh) -> int:
    n = 1
    for a in dp_axis_names(mesh):
        n *= mesh.shape[a]
    return n
