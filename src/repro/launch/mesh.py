"""Production mesh construction (multi-pod dry-run brief, step 1).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; callers (dryrun.py) set
``--xla_force_host_platform_device_count`` before any jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests/elastic runs.  Every axis is ``Auto``:
    the models place arrays through ``NamedSharding`` and sharding
    constraints and let the partitioner propagate the rest, which
    explicit-sharding axes reject (e.g. a gather from a sharded
    embedding table)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants for the roofline (assignment brief).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
