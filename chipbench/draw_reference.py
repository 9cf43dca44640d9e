"""A field drawn from a seed together with its exact log-likelihood.

At n = 65536 one fp64 factorization of the covariance holds 34 GB on the
host and takes minutes.  `data.field` factors Sigma(theta) to draw the
field and drops the factor; `reference.loglik` would factor the same
matrix again.  Here the draw keeps its factor L just long enough to give
the exact Gaussian log-likelihood of the float32 field at the theta it
was drawn at,

    ll_ref = -n/2 log(2 pi) - sum(log L_ii) - 1/2 ||L^{-1} z32||^2,

which is `reference.loglik` of the same sites, field and theta, with an
O(n^2) solve in place of a second factorization.  The sites and the
field are those `data.field` gives for the same arguments, bit for bit.
NumPy and LAPACK in float64; nothing of the program is imported.
"""

from __future__ import annotations

import functools

import numpy as np

from . import data, linalg


@functools.lru_cache(maxsize=2)
def _field_and_loglik(seed: int, stream: int, n: int, theta: tuple, nu: float,
                      jitter: float):
    gen = data.rng(seed, stream)
    locs = data.perturbed_grid(gen, n).astype(np.float32)
    locs = locs[data.morton_order(locs)]
    eps = gen.standard_normal(n)
    cov = linalg.covariance(locs, locs, theta, nu, jitter=jitter)
    u = linalg.cholesky_inplace(cov)
    z = linalg.lower_matvec(u, eps).astype(np.float32)
    logdet_half = np.sum(np.log(np.diagonal(u)))
    w = linalg.lower_solve(u, z)
    ll_ref = float(-0.5 * n * np.log(2.0 * np.pi) - logdet_half
                   - 0.5 * np.dot(w, w))
    return locs, z, ll_ref


def field_and_loglik(seed: int, stream: int, n: int, theta, nu: float,
                     jitter: float):
    """(locs float32 (n, 2), z float32 (n,), ll_ref float): the sites and
    the exact draw of `data.field(seed, stream, n, theta, nu, jitter)`,
    and the exact log-likelihood of z at theta.  The last two draws are
    kept (small arrays; the factor is not), so a second variant of the
    program on the same seed draws nothing again."""
    theta = tuple(float(v) for v in np.asarray(theta, np.float64))
    return _field_and_loglik(int(seed), int(stream), int(n), theta,
                             float(nu), float(jitter))
