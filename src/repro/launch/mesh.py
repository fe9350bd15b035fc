"""Production meshes.

Single pod:  (16, 16)      axes (data, model)        = 256 chips of v5e
Multi-pod:   (2, 16, 16)   axes (pod, data, model)   = 512 chips

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_small_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_small_mesh(shape=(2, 2), axes=("data", "model")):
    """Reduced mesh for CPU tests (requires enough host devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


class HW:
    """TPU v5e hardware constants for the roofline model."""
    PEAK_BF16_FLOPS = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link (~ per-exchange budget)
    HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
