"""Production mesh construction (brief-mandated shapes).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """A mesh whose axes are all Auto: the partitioner propagates shardings
    and ``with_sharding_constraint`` pins them (JAX's own default is
    Explicit axes, where shardings are part of every array's type)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def chips(mesh) -> int:
    import math
    return math.prod(mesh.devices.shape)
