"""The plain reference: exact fp64 answers for what the program computes.

Built from the configuration's own statement of the model (Matern
covariance at theta with the stated nu and diagonal jitter, Gaussian
log-likelihood of paper Eq. 2, kriging mean) on the same inputs the
program was given, in float64 on the host.  It imports nothing of the
program and uses nothing the program made.
"""

from __future__ import annotations

import numpy as np

from . import linalg


def loglik(locs, z, theta, nu: float, jitter: float) -> float:
    """Exact Gaussian log-likelihood of z at theta."""
    z = np.asarray(z, np.float64)
    n = z.shape[0]
    cov = linalg.covariance(locs, locs, theta, nu, jitter=jitter)
    u = linalg.cholesky_inplace(cov)
    logdet_half = np.sum(np.log(np.diagonal(u)))
    w = linalg.lower_solve(u, z)
    return float(-0.5 * n * np.log(2.0 * np.pi) - logdet_half
                 - 0.5 * np.dot(w, w))


def krige_mean(locs_obs, z_obs, locs_new, theta, nu: float,
               jitter: float) -> np.ndarray:
    """Exact kriging mean Sigma_no Sigma_oo^{-1} z at the new sites."""
    cov = linalg.covariance(locs_obs, locs_obs, theta, nu, jitter=jitter)
    u = linalg.cholesky_inplace(cov)
    cross = linalg.covariance(locs_new, locs_obs, theta, nu)
    w = linalg.lower_solve(u, z_obs)
    v = linalg.lower_solve(u, cross.T.copy(order="F"))
    return v.T @ w


def ll_drift(ll: float, ll_ref: float) -> float:
    """|ll - ll_ref| / |ll_ref|; infinite where ll is not finite."""
    if not np.isfinite(ll):
        return float("inf")
    return abs(float(ll) - ll_ref) / abs(ll_ref)


def mean_rel_err(mu, mu_ref) -> float:
    """||mu - mu_ref||_2 / ||mu_ref||_2; infinite where mu is not finite."""
    mu = np.asarray(mu, np.float64)
    if not np.all(np.isfinite(mu)):
        return float("inf")
    return float(np.linalg.norm(mu - mu_ref) / np.linalg.norm(mu_ref))
