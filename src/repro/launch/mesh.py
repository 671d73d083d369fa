"""Production meshes. A FUNCTION, not a module constant — importing this
module must never touch jax device state (smoke tests see 1 CPU device;
only dryrun.py requests 512 placeholder devices via XLA_FLAGS)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e pod slice); 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires XLA_FLAGS host device count ≥ prod)."""
    return _make_mesh(shape, axes)
