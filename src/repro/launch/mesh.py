"""Production mesh construction (multi-pod dry-run spec).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state. Call only after the process has its device
topology configured (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax


def _mk(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh for tests/elastic rescale."""
    return _mk(shape, axes)


def mesh_chips(mesh) -> int:
    n = 1
    for v in dict(mesh.shape).values():
        n *= v
    return n
