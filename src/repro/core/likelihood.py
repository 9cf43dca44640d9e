"""Gaussian log-likelihood evaluation (paper Eqs. 2-3) on tile Cholesky.

One likelihood evaluation = build Sigma(theta) from the Matern kernel,
factor it with the selected precision policy, then

  l(theta) = -n/2 log(2 pi) - sum_i log L_ii - 1/2 || L^{-1} Z ||^2 .

The profiled form (Eq. 3) treats theta1 as a multiplicative scale computed
in closed form, leaving a 2-parameter optimization over (theta2, theta3):

  theta1_opt = Z^T SigmaTilde^{-1} Z / n,
  l* = -n/2 log(2 pi) - n/2 - n/2 log(theta1_opt) - log|L-tilde| .
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..covariance.matern import matern_covariance
from .precision import PrecisionPolicy
from .tile_cholesky import dst_cholesky, reference_cholesky, tile_cholesky


def _forward_solve_vec(l, z):
    """w = L^{-1} z with l (..., n, n) and z (n,); returns (..., n)."""
    zb = jnp.broadcast_to(z, l.shape[:-2] + z.shape[-1:])
    return solve_triangular(l, zb[..., None], lower=True)[..., 0]


def loglik_from_factor(l, z):
    """Eq. 2 given the lower Cholesky factor of Sigma.

    l may carry leading batch axes (one factor per candidate theta); the
    result then has those batch axes.
    """
    n = z.shape[-1]
    z = z.astype(l.dtype)
    diag = jnp.diagonal(l, axis1=-2, axis2=-1)
    logdet_half = jnp.sum(jnp.log(diag), axis=-1)
    w = _forward_solve_vec(l, z)
    quad = jnp.sum(w * w, axis=-1)
    return -0.5 * n * jnp.log(2.0 * jnp.pi) - logdet_half - 0.5 * quad


def profiled_loglik_from_factor(l, z):
    """Eq. 3: profile out theta1. `l` factors the CORRELATION matrix."""
    n = z.shape[-1]
    z = z.astype(l.dtype)
    diag = jnp.diagonal(l, axis1=-2, axis2=-1)
    logdet_half = jnp.sum(jnp.log(diag), axis=-1)
    w = _forward_solve_vec(l, z)
    theta1_opt = jnp.sum(w * w, axis=-1) / n
    ll = (-0.5 * n * jnp.log(2.0 * jnp.pi) - 0.5 * n
          - 0.5 * n * jnp.log(theta1_opt) - logdet_half)
    return ll, theta1_opt


def dst_loglik(blocks, z):
    """Eq. 2 for the block-diagonal DST factor (independent blocks).

    Block factors may carry leading batch axes, like loglik_from_factor.
    """
    n = z.shape[-1]
    total = -0.5 * n * jnp.log(2.0 * jnp.pi)
    for sl, l in blocks:
        zb = z[sl].astype(l.dtype)
        diag = jnp.diagonal(l, axis1=-2, axis2=-1)
        w = _forward_solve_vec(l, zb)
        total = total - jnp.sum(jnp.log(diag), axis=-1) - 0.5 * jnp.sum(w * w, axis=-1)
    return total


def build_covariance(locs, theta, *, nu_static=None, metric="euclidean",
                     nugget=0.0, jitter=0.0, dtype=None):
    cov = matern_covariance(locs, locs, theta, nu_static=nu_static,
                            metric=metric, nugget=nugget)
    if jitter:
        cov = cov + jitter * jnp.eye(cov.shape[-1], dtype=cov.dtype)
    if dtype is not None:
        cov = cov.astype(dtype)
    return cov


def make_factor_fn(locs, policy: PrecisionPolicy, *, nb: int = 128,
                   nu_static=None, metric="euclidean", nugget=0.0,
                   jitter=1e-6, use_tiles=None):
    """Return theta -> lower Cholesky factor of Sigma(theta).

    This is THE covariance-build + factor-path selection (tiled Algorithm 1
    vs dense reference, per `use_tiles`/policy mode), shared by `make_loglik`
    and the batch engine's fused evaluate so the two can never diverge.
    Not applicable to mode="dst" (block factors; see `dst_cholesky`).
    """
    if policy.mode == "dst":
        raise ValueError("dst mode factors independent blocks; "
                         "use dst_cholesky")
    locs = jnp.asarray(locs)
    tiled = use_tiles if use_tiles is not None else policy.mode != "full"

    def factor(theta):
        with jax.named_scope("cov_build"):
            cov = build_covariance(locs, jnp.asarray(theta),
                                   nu_static=nu_static, metric=metric,
                                   nugget=nugget, jitter=jitter,
                                   dtype=policy.hi)
        with jax.named_scope("factor"):
            return tile_cholesky(cov, nb, policy) if tiled \
                else reference_cholesky(cov, policy.hi)

    return factor


def make_loglik(locs, z, policy: PrecisionPolicy, *, nb: int = 128,
                nu_static=None, metric="euclidean", nugget=0.0,
                jitter=1e-6, profiled=False, use_tiles=None):
    """Return theta -> log-likelihood under the given precision policy.

    use_tiles: force the tile path even for mode="full" (None = auto: tile
    path for mixed/three_tier, plain LAPACK-style for full).

    The returned closure accepts a single theta (3,) or a stacked batch
    (..., 3) of candidates, returning matching leading axes of
    log-likelihoods (one factorization per candidate, batched tile ops).
    """
    locs = jnp.asarray(locs)
    z = jnp.asarray(z)
    factor = None if policy.mode == "dst" else make_factor_fn(
        locs, policy, nb=nb, nu_static=nu_static, metric=metric,
        nugget=nugget, jitter=jitter, use_tiles=use_tiles)

    def loglik(theta):
        theta = jnp.asarray(theta)
        cov_theta = jnp.concatenate(
            [jnp.ones_like(theta[..., :1]), theta[..., :2]], axis=-1) \
            if profiled else theta
        if policy.mode == "dst":
            if profiled:
                raise NotImplementedError("profiled DST not needed")
            cov = build_covariance(locs, cov_theta, nu_static=nu_static,
                                   metric=metric, nugget=nugget,
                                   jitter=jitter, dtype=policy.hi)
            blocks = dst_cholesky(cov, nb, policy.diag_thick, hi=policy.hi)
            return dst_loglik(blocks, z)
        l = factor(cov_theta)
        if profiled:
            ll, _ = profiled_loglik_from_factor(l, z)
            return ll
        return loglik_from_factor(l, z)

    return loglik
