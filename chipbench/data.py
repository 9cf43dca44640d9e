"""Locations and fields of the synthetic study, drawn from a seed.

ExaGeoStat's generator, as the paper describes it (Sec. VIII-B1): a
sqrt(n) x sqrt(n) grid in the unit square, each point moved by uniform
jitter of up to 0.4 of the spacing; the field is an exact draw
Z = L eps with Sigma(theta0) = L L^T (Matern, fp64 on the host); the
sites are sorted along the Morton (Z-order) curve.  Everything comes
from NumPy's PCG64 seeded with (seed, stream), so one seed gives the
same sites, field and traffic on every machine.
"""

from __future__ import annotations

import math

import numpy as np

from . import linalg


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one use of one seed."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def perturbed_grid(gen: np.random.Generator, n: int) -> np.ndarray:
    """n irregular sites in (0, 1)^2: the first n of an m x m jittered grid."""
    m = math.ceil(math.sqrt(n))
    xs, ys = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    grid = np.stack([xs.ravel(), ys.ravel()], axis=-1).astype(np.float64)
    jitter = gen.uniform(-0.4, 0.4, size=(m * m, 2))
    return ((grid + 0.5 + jitter) / m)[:n]


def _part1by1(x: np.ndarray) -> np.ndarray:
    x = x & 0x0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    return (x | (x << 1)) & 0x55555555


def morton_order(locs: np.ndarray, bits: int = 16) -> np.ndarray:
    """Permutation that sorts sites in [0, 1)^2 along the Morton curve."""
    scale = (1 << bits) - 1
    q = np.clip((np.asarray(locs) * scale).astype(np.uint64), 0, scale)
    key = _part1by1(q[:, 0]) | (_part1by1(q[:, 1]) << 1)
    return np.argsort(key, kind="stable")


def field(seed: int, stream: int, n: int, theta0, nu: float, jitter: float):
    """(locs float32 (n, 2), z float32 (n,)): Morton-ordered sites and an
    exact draw of the zero-mean Matern field at theta0."""
    gen = rng(seed, stream)
    locs = perturbed_grid(gen, n).astype(np.float32)
    locs = locs[morton_order(locs)]
    eps = gen.standard_normal(n)
    cov = linalg.covariance(locs, locs, theta0, nu, jitter=jitter)
    u = linalg.cholesky_inplace(cov)
    z = linalg.lower_matvec(u, eps).astype(np.float32)
    return locs, z


def box_draw(gen: np.random.Generator, box, count: int) -> np.ndarray:
    """`count` points uniform in the box [[lo, hi], ...], float32."""
    box = np.asarray(box, np.float64)
    return gen.uniform(box[:, 0], box[:, 1],
                       size=(count, box.shape[0])).astype(np.float32)
