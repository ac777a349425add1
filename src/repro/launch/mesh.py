"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto (GSPMD-propagated) axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the local device (smoke/bench paths)."""
    return make_mesh((1, 1), ("data", "model"))


def data_shards(mesh) -> int:
    n = mesh.shape.get("data", 1)
    return n * mesh.shape.get("pod", 1)
