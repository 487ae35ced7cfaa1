"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple[AxisType, ...]:
    # jax.make_mesh defaults to Explicit axes, under which the logical
    # ``with_sharding_constraint`` rules (sharding/specs.py) raise; these
    # meshes are driven by sharding constraints, so their axes are Auto.
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(
    shape: tuple[int, ...] | None = None, axes: tuple[str, ...] | None = None
):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if n > 1 else (1, 1)
        axes = ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
