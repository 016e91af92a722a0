"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; ``pod`` is an outer
data-parallel axis whose collectives ride DCI between pods.

Functions (never module-level constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the logical-axis hooks (with_sharding_constraint) and
    # shard_map both need them; jax.make_mesh defaults to Explicit
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return _auto_mesh((data, model), ("data", "model"))


# v5e hardware constants (roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~4 links/chip on v5e 2D torus)
HBM_PER_CHIP = 16 * 1024 ** 3   # 16 GiB
