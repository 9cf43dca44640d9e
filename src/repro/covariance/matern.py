"""Matern covariance function (paper Eq. 1) in pure JAX.

C(r; theta) = theta1 * 2^(1-nu)/Gamma(nu) * (r/theta2)^nu * K_nu(r/theta2)

with theta = (theta1: variance, theta2: spatial range, theta3 = nu: smoothness).

K_nu is the modified Bessel function of the second kind.  It is not provided
by jax.scipy.special, so we implement it here:

  * closed forms for the half-integer smoothnesses nu in {0.5, 1.5, 2.5}
    (exponential x polynomial) -- these are the cases used for the paper's
    synthetic study and are cheap enough to live inside Pallas kernels;
  * a general-nu path (needed for the real-data regime, nu-hat ~ 1.1-1.4)
    following Numerical Recipes `bessik`: Temme's series for x <= 2 and the
    Steed/CF2 continued fraction for x > 2, then masked upward recurrence.
    All loops have static trip counts so the function jits/vmaps/grads.

Validated against scipy.special.kv in tests/test_matern.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

# Static bounds: series/CF iteration counts and max smoothness.
_MAXIT = 80
_NU_MAX_RECURRENCE = 12  # supports nu < 11.5; geostatistics uses nu < 5
_EULER_GAMMA = 0.5772156649015329

# Chebyshev coefficients (Numerical Recipes `beschb`) for
#   gam1(mu) ~ (1/Gamma(1-mu) - 1/Gamma(1+mu)) / (2 mu)
#   gam2(mu) ~ (1/Gamma(1-mu) + 1/Gamma(1+mu)) / 2        for |mu| <= 1/2.
_C1 = (
    -1.142022680371168e0,
    6.5165112670737e-3,
    3.087090173086e-4,
    -3.4706269649e-6,
    6.9437664e-9,
    3.67795e-11,
    -1.356e-13,
)
_C2 = (
    1.843740587300905e0,
    -7.68528408447867e-2,
    1.2719271366546e-3,
    -4.9717367042e-6,
    -3.31261198e-8,
    2.423096e-10,
    -1.702e-13,
    -1.49e-15,
)


def _chebev(coeffs: tuple, x):
    """Chebyshev series evaluation on [-1, 1] (Clenshaw).

    coeffs stay Python floats (weak-typed) so the series runs at x's dtype
    -- including fp64 under `jax.enable_x64(True)`.
    """
    d = jnp.zeros_like(x)
    dd = jnp.zeros_like(x)
    x2 = 2.0 * x
    for c in coeffs[::-1][:-1]:
        d, dd = x2 * d - dd + c, d
    return x * d - dd + 0.5 * coeffs[0]


def _beschb(mu):
    """gam1, gam2, gampl=1/Gamma(1+mu), gammi=1/Gamma(1-mu) for |mu|<=0.5."""
    xx = 8.0 * mu * mu - 1.0
    gam1 = _chebev(_C1, xx)
    gam2 = _chebev(_C2, xx)
    gampl = gam2 - mu * gam1
    gammi = gam2 + mu * gam1
    return gam1, gam2, gampl, gammi


def _kv_temme_series(nu_frac, x):
    """K_mu(x), K_{mu+1}(x) for x <= 2, mu = nu_frac in [-0.5, 0.5]."""
    mu = nu_frac
    x = jnp.minimum(x, 2.0)  # branch-safe clamp (selection happens outside)
    pimu = jnp.pi * mu
    fact = jnp.where(jnp.abs(pimu) < 1e-7, 1.0, pimu / jnp.sin(jnp.where(jnp.abs(pimu) < 1e-7, 1.0, pimu)))
    d = -jnp.log(x / 2.0)
    e = mu * d
    fact2 = jnp.where(jnp.abs(e) < 1e-7, 1.0, jnp.sinh(e) / jnp.where(jnp.abs(e) < 1e-7, 1.0, e))
    gam1, gam2, gampl, gammi = _beschb(mu)
    ff = fact * (gam1 * jnp.cosh(e) + gam2 * fact2 * d)
    ssum = ff
    e = jnp.exp(e)
    p = 0.5 * e / gampl
    q = 0.5 / (e * gammi)
    c = jnp.ones_like(x)
    dd = x * x / 4.0
    sum1 = p

    def body(i, carry):
        ff, ssum, sum1, c, p, q = carry
        fi = i.astype(x.dtype)
        ff = (fi * ff + p + q) / (fi * fi - mu * mu)
        c = c * dd / fi
        p = p / (fi - mu)
        q = q / (fi + mu)
        ssum = ssum + c * ff
        sum1 = sum1 + c * (p - fi * ff)
        return ff, ssum, sum1, c, p, q

    carry = (ff, ssum, sum1, c, p, q)
    carry = jax.lax.fori_loop(1, _MAXIT + 1, body, carry)
    _, ssum, sum1, _, _, _ = carry
    rkmu = ssum
    rk1 = sum1 * (2.0 / x)
    return rkmu, rk1


def _kv_cf2(nu_frac, x):
    """K_mu(x), K_{mu+1}(x) for x > 2 via Steed's CF2 (NR bessik)."""
    mu = nu_frac
    x = jnp.maximum(x, 2.0)  # branch-safe clamp
    b = 2.0 * (1.0 + x)
    d = 1.0 / b
    h = d
    delh = d
    q1 = jnp.zeros_like(x)
    q2 = jnp.ones_like(x)
    a1 = 0.25 - mu * mu
    q = a1 * jnp.ones_like(x)
    c = a1 * jnp.ones_like(x)
    a = -a1 * jnp.ones_like(x)
    s = 1.0 + q * delh

    eps = jnp.finfo(x.dtype).eps
    done0 = jnp.zeros_like(x, dtype=bool)

    def body(i, carry):
        a, b, c, d, h, delh, q, q1, q2, s, done = carry
        fi = i.astype(x.dtype)
        a_n = a - 2.0 * (fi - 1.0)
        c_n = -a_n * c / fi
        qnew = (q1 - b * q2) / a_n
        q_n = q + c_n * qnew
        b_n = b + 2.0
        d_n = 1.0 / (b_n + a_n * d)
        delh_n = (b_n * d_n - 1.0) * delh
        h_n = h + delh_n
        dels = q_n * delh_n
        s_n = s + dels
        # freeze all state after convergence: running a fixed-trip-count
        # loop past convergence overflows q1/q2 in fp32 (NR breaks instead)
        sel = lambda new, old: jnp.where(done, old, new)
        new_done = done | (jnp.abs(dels) < jnp.abs(s_n) * eps)
        return (sel(a_n, a), sel(b_n, b), sel(c_n, c), sel(d_n, d),
                sel(h_n, h), sel(delh_n, delh), sel(q_n, q),
                sel(q2, q1), sel(qnew, q2), sel(s_n, s), new_done)

    carry = (a, b, c, d, h, delh, q, q1, q2, s, done0)
    carry = jax.lax.fori_loop(2, _MAXIT + 1, body, carry)
    a, b, c, d, h, delh, q, q1, q2, s, _ = carry
    h = a1 * h
    rkmu = jnp.sqrt(jnp.pi / (2.0 * x)) * jnp.exp(-x) / s
    rk1 = rkmu * (mu + x + 0.5 - h) / x
    return rkmu, rk1


def kv(nu, x):
    """Modified Bessel function of the second kind K_nu(x), elementwise.

    nu: scalar or array broadcastable against x (may be traced), nu >= 0,
        nu < _NU_MAX_RECURRENCE - 0.5.
    x:  array, x > 0.  Gradients flow through both arguments' jnp ops.
    """
    nu = jnp.asarray(nu)
    x = jnp.asarray(x)
    dtype = jnp.result_type(nu.dtype, x.dtype, jnp.float32)
    nu = nu.astype(dtype)
    x = jnp.maximum(x.astype(dtype), jnp.finfo(dtype).tiny)

    nl = jnp.floor(nu + 0.5)  # number of upward-recurrence steps
    mu = nu - nl  # fractional part in [-0.5, 0.5]

    small = x <= 2.0
    rkmu_s, rk1_s = _kv_temme_series(mu, x)
    rkmu_l, rk1_l = _kv_cf2(mu, x)
    rkmu = jnp.where(small, rkmu_s, rkmu_l)
    rk1 = jnp.where(small, rk1_s, rk1_l)

    # Masked upward recurrence K_{mu+i+1} = 2(mu+i)/x K_{mu+i} + K_{mu+i-1}.
    xi2 = 2.0 / x

    def rec(i, carry):
        rkmu, rk1 = carry
        fi = i.astype(dtype)
        take = fi <= nl
        rktemp = (mu + fi) * xi2 * rk1 + rkmu
        rkmu = jnp.where(take, rk1, rkmu)
        rk1 = jnp.where(take, rktemp, rk1)
        return rkmu, rk1

    rkmu, rk1 = jax.lax.fori_loop(1, _NU_MAX_RECURRENCE, rec, (rkmu, rk1))
    return rkmu


def _matern_half_integer(r_over_rho, nu: float):
    """Closed-form 2^(1-nu)/Gamma(nu) x^nu K_nu(x) for half-integer nu."""
    x = r_over_rho
    if nu == 0.5:
        return jnp.exp(-x)
    if nu == 1.5:
        return (1.0 + x) * jnp.exp(-x)
    if nu == 2.5:
        return (1.0 + x + x * x / 3.0) * jnp.exp(-x)
    raise ValueError(f"no closed form for nu={nu}")


HALF_INTEGER_NUS = (0.5, 1.5, 2.5)


def matern(r, theta, *, nu_static: float | None = None):
    """Matern covariance C(r; theta), paper Eq. (1).

    r: distances (any shape), theta = (theta1, theta2, theta3) or a stacked
      (..., 3) batch of parameter vectors: leading axes of theta broadcast
      against r, producing one covariance per candidate theta (the batched
      likelihood engine relies on this).
    nu_static: if one of HALF_INTEGER_NUS, use the closed form and IGNORE
      theta[..., 2] (the caller promises theta3 == nu_static); otherwise the
      general Bessel path with traced smoothness theta[..., 2] is used.
    """
    theta = jnp.asarray(theta)
    r = jnp.asarray(r)
    # reshape each parameter to (batch..., 1, ..., 1) so it broadcasts
    # against r regardless of r's rank
    batch = theta.shape[:-1]

    def param(i):
        return theta[..., i].reshape(batch + (1,) * r.ndim)

    theta1, theta2 = param(0), param(1)
    x = r / theta2
    if nu_static is not None:
        corr = _matern_half_integer(x, float(nu_static))
        return theta1 * jnp.where(r == 0.0, 1.0, corr)

    nu = param(2)
    xs = jnp.maximum(x, 1e-30)  # keep kv's domain valid at r == 0
    lognorm = (1.0 - nu) * jnp.log(2.0) - gammaln(nu)
    corr = jnp.exp(lognorm + nu * jnp.log(xs)) * kv(nu, xs)
    return theta1 * jnp.where(r == 0.0, 1.0, corr)


def pairwise_distance(locs_a, locs_b, *, metric: str = "euclidean"):
    """Pairwise distance matrix between two (n, 2) location sets.

    metric: "euclidean" (synthetic study, unit square) or "haversine"
    (real datasets on lon/lat degrees; great-circle distance in degrees,
    matching ExaGeoStat's use of the haversine formula [paper ref 31]).
    """
    if metric == "euclidean":
        d2 = jnp.sum((locs_a[:, None, :] - locs_b[None, :, :]) ** 2, axis=-1)
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    if metric == "haversine":
        lon_a, lat_a = jnp.deg2rad(locs_a[:, 0]), jnp.deg2rad(locs_a[:, 1])
        lon_b, lat_b = jnp.deg2rad(locs_b[:, 0]), jnp.deg2rad(locs_b[:, 1])
        dlat = lat_a[:, None] - lat_b[None, :]
        dlon = lon_a[:, None] - lon_b[None, :]
        h = (
            jnp.sin(dlat / 2.0) ** 2
            + jnp.cos(lat_a)[:, None] * jnp.cos(lat_b)[None, :] * jnp.sin(dlon / 2.0) ** 2
        )
        h = jnp.clip(h, 0.0, 1.0)
        # 2 R asin(sqrt(h)); report in "degrees" (R = 180/pi) so theta2 is
        # on the same scale as the paper's Table I estimates.
        return 2.0 * (180.0 / jnp.pi) * jnp.arcsin(jnp.sqrt(h))
    raise ValueError(f"unknown metric {metric!r}")


def matern_covariance(locs_a, locs_b, theta, *, nu_static: float | None = None,
                      metric: str = "euclidean", nugget: float = 0.0):
    """Dense covariance block Sigma_ab with optional nugget on the diagonal.

    theta may carry leading batch axes (see `matern`); the result is then a
    (..., n_a, n_b) stack of covariance blocks.
    """
    d = pairwise_distance(locs_a, locs_b, metric=metric)
    cov = matern(d, theta, nu_static=nu_static)
    if nugget:
        n = min(cov.shape[-2], cov.shape[-1])
        idx = jnp.arange(n)
        cov = cov.at[..., idx, idx].add(nugget)
    return cov
