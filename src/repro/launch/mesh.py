"""Production mesh construction (multi-pod dry-run spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first backend init)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


# TPU v5e-class hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # B/s per chip
ICI_BW = 50e9                 # B/s per link
HBM_BYTES = 16 * 2**30        # 16 GB per chip
