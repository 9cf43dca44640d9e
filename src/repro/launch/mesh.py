"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (critical: tests must see 1 CPU device; only dryrun.py
forces 512 placeholder devices via XLA_FLAGS before any jax import).

Topology: TPU v5e pods, 16x16 = 256 chips per pod.
  single pod : (16, 16)    axes ("data", "model")
  multi pod  : (2, 16, 16) axes ("pod", "data", "model") -- "pod" is the
               DCN-connected second data-parallel tier.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(n: int) -> tuple:
    # the sharding code (constrain, the dry-run's in/out shardings) is
    # written for Auto axes; jax.make_mesh defaults to Explicit ones
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_smoke_mesh():
    """1x1 mesh with production axis names: same model/sharding code paths
    on a single CPU device."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def make_geostat_mesh(devices):
    """("data", "model") Auto mesh over 4 devices as 2x2 (one v5e host, or
    a described `v5e:2x2` topology's devices for compile-only checks)."""
    devices = np.asarray(list(devices)).reshape(2, 2)
    return Mesh(devices, ("data", "model"), axis_types=_auto(2))


def mesh_num_devices(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_BF16_FLOPS = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_LINK_BW = 50e9              # B/s per link
