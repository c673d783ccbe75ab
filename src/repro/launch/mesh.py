"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — critical because
the dry-run must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the launchers place arrays with
    ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (run under xla_force_host_platform_device_count)."""
    return auto_mesh(shape, axes)
