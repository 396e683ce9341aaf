"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only
``dryrun.py`` forces the 512-placeholder configuration)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e); multi_pod adds a 2-pod outer axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4, *,
                    multi_pod: bool = False):
    """Small mesh for CI-scale dry-run tests (8-16 host devices)."""
    if multi_pod:
        return _make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _make_mesh((n_data, n_model), ("data", "model"))
