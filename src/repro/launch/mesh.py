"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512
host devices before any jax import).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the sharding rules in sharding.py
    place arrays with ``with_sharding_constraint``, which Explicit axes
    (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for in-test dry-runs (requires forced host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh: ('pod','data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in dp_axes(mesh))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
