"""fp64 reference answers and error metrics for the verification stack.

Oracle convention: every oracle upcasts the SAME fp32 input matrix the
mixed-precision path factors (rather than rebuilding the covariance in
fp64), so the measured error isolates the factorization/solve chain from
covariance-build rounding.  All oracle arithmetic runs in fp64 on the
host CPU device (`jax.enable_x64(True)` pinned to `jax.devices("cpu")`, or
NumPy/SciPy), never on the default device: an accelerator without fp64
would silently answer in fp32.  All metrics are computed in fp64.

Metrics (the quantities the tolerance registry bounds):

  rel_frobenius(l, l_ref)   forward factor error ||L - L_ref||_F / ||L_ref||_F
  backward_error(l, a)      reconstruction error ||L L^T - A||_F / ||A||_F
  loglik_drift(ll, ll_ref)  |ll - ll_ref| / max(1, |ll_ref|)
  pmse_drift(p, p_ref)      |pmse - pmse_ref| / pmse_ref
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from scipy.linalg import solve_triangular

# ---------------------------------------------------------------------------
# fp64 reference answers
# ---------------------------------------------------------------------------


def exact_factor(cov) -> np.ndarray:
    """fp64 dense lower Cholesky of (the upcast of) `cov`, on the host CPU."""
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        a = jax.device_put(np.asarray(cov, np.float64), cpu)
        l = jnp.linalg.cholesky(a)
        if l.dtype != jnp.float64 or l.devices() != {cpu}:
            raise RuntimeError(f"fp64 oracle answered in {l.dtype} on "
                               f"{l.devices()}, not fp64 on {cpu}")
        return np.asarray(l, np.float64)


def exact_loglik(cov, z) -> float:
    """Exact Gaussian log-likelihood (paper Eq. 2) in fp64."""
    a = np.asarray(cov, np.float64)
    zz = np.asarray(z, np.float64)
    l = exact_factor(a)
    n = zz.shape[-1]
    w = solve_triangular(l, zz, lower=True)
    return float(-0.5 * n * np.log(2.0 * np.pi)
                 - np.sum(np.log(np.diag(l))) - 0.5 * np.sum(w * w))


def exact_kriging_pmse(cov_oo, z_obs, sigma_no, y_true) -> float:
    """Exact kriging PMSE in fp64, independent of the policy machinery.

    cov_oo: (n, n) observed-observed covariance (jitter included);
    sigma_no: (m, n) cross covariance; y_true: (m,) held-out truth.
    """
    a = np.asarray(cov_oo, np.float64)
    z = np.asarray(z_obs, np.float64)
    c = np.asarray(sigma_no, np.float64)
    y = np.asarray(y_true, np.float64)
    mu = c @ np.linalg.solve(a, z)
    return float(np.mean((mu - y) ** 2))


# ---------------------------------------------------------------------------
# error metrics
# ---------------------------------------------------------------------------


def rel_frobenius(a, ref) -> float:
    """Relative Frobenius distance ||a - ref||_F / ||ref||_F in fp64."""
    a64 = np.asarray(a, np.float64)
    r64 = np.asarray(ref, np.float64)
    denom = np.linalg.norm(r64)
    return float(np.linalg.norm(a64 - r64) / max(denom, np.finfo(np.float64).tiny))


def backward_error(l, a) -> float:
    """Reconstruction (backward) error ||L L^T - A||_F / ||A||_F in fp64."""
    l64 = np.asarray(l, np.float64)
    return rel_frobenius(l64 @ l64.T, np.asarray(a, np.float64))


def loglik_drift(ll, ll_ref) -> float:
    """Log-likelihood drift, normalized so it reads like a relative error
    but stays meaningful when ll_ref crosses zero."""
    ll = float(ll)
    ll_ref = float(ll_ref)
    return abs(ll - ll_ref) / max(1.0, abs(ll_ref))


def pmse_drift(p, p_ref) -> float:
    """Relative PMSE drift vs the fp64 exact predictor."""
    return abs(float(p) - float(p_ref)) / max(float(p_ref),
                                              np.finfo(np.float64).tiny)
