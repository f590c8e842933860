"""Production meshes.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device count is locked at first jax init, and
only launch/dryrun.py is allowed to fake 512 host devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1,) * (len(axes) - 1) + (n,)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (roofline targets, EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
