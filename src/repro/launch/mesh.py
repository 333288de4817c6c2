"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests, which must see
exactly one device.  Every mesh in this repo is Auto-typed.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: "pod" (outer data parallel, crosses DCN), "data" (data parallel /
    ZeRO shard axis), "model" (tensor/expert parallel, stays inside an ICI
    torus dimension).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) local devices exist."""
    return make_mesh((data, model), ("data", "model"))
