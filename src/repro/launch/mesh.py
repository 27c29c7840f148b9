"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before first jax init; tests and benches see the single real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_cpu_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; (2, 16, 16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_cpu_mesh():
    """Degenerate 1x1 mesh over the single real CPU device (smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
