"""Production mesh construction (DESIGN.md §5).

single-pod: (16, 16)    axes (data, model)        — 256 chips (one v5e pod)
multi-pod:  (2, 16, 16) axes (pod, data, model)   — 512 chips (2 pods)

A *function*, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import;
tests and benches see the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a gather
    on a sharded operand (the slab-row gathers of the cache step, the
    embedding lookup of the model) raises ``ShardingTypeError``. The cache
    and model code leave sharding propagation to the compiler, so every
    mesh in this repository is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires forced host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a == "model")
