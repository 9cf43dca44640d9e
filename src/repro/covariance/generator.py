"""Synthetic geostatistical data generation (ExaGeoStat's generator).

Mirrors the data generator described in the paper (Sec. VIII-B1) and in
Abdulah et al. 2018 [paper ref 32]:

  1. irregular 2-D locations: a sqrt(n) x sqrt(n) grid in (0, 1)^2 perturbed
     by uniform jitter (so locations are irregular but well-spread);
  2. measurements Z = L eps with Sigma(theta0) = L L^T from the Matern
     kernel and eps ~ N(0, I).

Also provides the WRF-like "wind speed" simulator used for the Table-I
reproduction: since the real Middle-East WRF dataset is not redistributable
(and there is no network access), we *simulate* a field per region with the
Matern parameters the paper reports in Table I, then re-estimate them --
validating estimator consistency exactly the way the paper's Table I does.
This substitution is recorded in DESIGN.md ("Changed assumptions").
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .matern import matern_covariance
from .ordering import ORDERINGS, apply_ordering


class Dataset(NamedTuple):
    locs: jnp.ndarray   # (n, 2)
    z: jnp.ndarray      # (n,)
    theta0: jnp.ndarray  # generating parameters (3,)
    metric: str


def random_locations(key, n: int, *, lo: float = 0.0, hi: float = 1.0,
                     dtype=jnp.float32):
    """Irregular perturbed-grid locations in (lo, hi)^2 (ExaGeoStat style)."""
    m = math.ceil(math.sqrt(n))  # host math: n is static under jit
    xs, ys = jnp.meshgrid(jnp.arange(m), jnp.arange(m), indexing="ij")
    grid = jnp.stack([xs.reshape(-1), ys.reshape(-1)], axis=-1).astype(dtype)
    jitter = jax.random.uniform(key, (m * m, 2), minval=-0.4, maxval=0.4,
                                dtype=dtype)
    locs = (grid + 0.5 + jitter) / m  # in (0, 1)^2
    locs = locs[:n]
    return lo + locs * (hi - lo)


def simulate_field(key, locs, theta0, *, nu_static=None, metric="euclidean",
                   nugget: float = 0.0, jitter: float = 1e-8):
    """Draw Z ~ N(0, Sigma(theta0)) exactly via dense Cholesky."""
    n = locs.shape[0]
    cov = matern_covariance(locs, locs, jnp.asarray(theta0), nu_static=nu_static,
                            metric=metric, nugget=nugget)
    cov = cov + jitter * jnp.eye(n, dtype=cov.dtype)
    chol = jnp.linalg.cholesky(cov)
    eps = jax.random.normal(key, (n,), dtype=cov.dtype)
    return chol @ eps


def make_dataset(key, n: int, theta0, *, nu_static=None, ordering: str = "morton",
                 metric: str = "euclidean", nugget: float = 0.0) -> Dataset:
    """Locations + field draw + space-filling-curve ordering, one call."""
    k_loc, k_field = jax.random.split(key)
    locs = random_locations(k_loc, n)
    z = simulate_field(k_field, locs, theta0, nu_static=nu_static, metric=metric,
                       nugget=nugget)
    perm = ORDERINGS[ordering](locs)
    locs, z = apply_ordering(locs, z, perm)
    return Dataset(locs=locs, z=z, theta0=jnp.asarray(theta0), metric=metric)


# Paper Sec. VIII-D1: three correlation levels for the synthetic study.
CORRELATION_LEVELS = {
    "weak": jnp.array([1.0, 0.03, 0.5]),
    "medium": jnp.array([1.0, 0.10, 0.5]),
    "strong": jnp.array([1.0, 0.30, 0.5]),
}


# Table-I Matern parameters per wind-speed region (theta1, theta2, theta3).
# R1's row is unreadable in the paper scan; we use values interpolated from
# R2-R4 (flagged in DESIGN.md).  theta2 is on the haversine-degrees scale.
WIND_REGIONS = {
    "R1": jnp.array([11.1, 24.0, 1.30]),
    "R2": jnp.array([12.533, 27.603, 1.270]),
    "R3": jnp.array([10.813, 19.196, 1.417]),
    "R4": jnp.array([12.441, 19.733, 1.119]),
}


def wind_like_dataset(key, region: str, n: int, *, ordering: str = "morton") -> Dataset:
    """WRF-like wind-speed field for one Arabian-Peninsula subregion.

    Locations are drawn on a lon/lat box roughly matching one quadrant of
    the paper's Fig. 3 domain; distances are haversine (degrees).
    """
    theta0 = WIND_REGIONS[region]
    boxes = {  # (lon_lo, lon_hi, lat_lo, lat_hi) quadrants of [30,60]x[10,35]
        "R1": (30.0, 45.0, 22.5, 35.0),
        "R2": (45.0, 60.0, 22.5, 35.0),
        "R3": (30.0, 45.0, 10.0, 22.5),
        "R4": (45.0, 60.0, 10.0, 22.5),
    }
    lon_lo, lon_hi, lat_lo, lat_hi = boxes[region]
    k_loc, k_field = jax.random.split(key)
    unit = random_locations(k_loc, n)
    locs = jnp.stack(
        [lon_lo + unit[:, 0] * (lon_hi - lon_lo), lat_lo + unit[:, 1] * (lat_hi - lat_lo)],
        axis=-1,
    )
    z = simulate_field(k_field, locs, theta0, metric="haversine", jitter=1e-6)
    # order on the unit-normalized coords
    perm = ORDERINGS[ordering]((locs - locs.min(0)) / (locs.max(0) - locs.min(0)))
    locs, z = apply_ordering(locs, z, perm)
    return Dataset(locs=locs, z=z, theta0=theta0, metric="haversine")
