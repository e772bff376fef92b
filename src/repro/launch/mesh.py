"""Production mesh definitions (TPU v5e-256 pods).

``make_production_mesh`` is a function (not module-level state) so importing
this module never touches jax device initialization — the dry-run sets
XLA_FLAGS before any jax import and only then builds meshes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with Auto axes (the sharding rules annotate with
    PartitionSpecs and let XLA propagate; they do not use explicit-axis
    sharding types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (fake) devices the test process has."""
    return make_mesh((data, model), ("data", "model"))
