"""Production mesh definition.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; callers (dryrun.py) set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before ANY jax
import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 v5e chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def _auto(axes) -> tuple:
    """Auto axis types: ``with_sharding_constraint`` (distributed/
    sharding.py) only accepts Auto mesh axes, and ``jax.make_mesh`` now
    defaults to Explicit ones."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))
