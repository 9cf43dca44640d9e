"""fp64 host arithmetic shared by the data draw and the plain reference.

NumPy and LAPACK on the host CPU, in float64.  Nothing here imports the
system under test.  Covariance blocks are built in row chunks on a few
threads (NumPy releases the interpreter lock inside its loops), and the
Cholesky factor is taken in place, so an n = 32768 matrix costs its
8.6 GB once.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.special
from scipy.linalg import blas, lapack

ROWS_PER_CHUNK = 256
THREADS = min(8, os.cpu_count() or 1)


def matern(r: np.ndarray, variance: float, rng: float, nu: float) -> np.ndarray:
    """Matern covariance (paper Eq. 1) of distances r, in place on r."""
    x = np.divide(r, rng, out=r)
    if nu == 0.5:
        np.negative(x, out=x)
        out = np.exp(x, out=x)
    elif nu == 1.5:
        out = (1.0 + x) * np.exp(-x)
    elif nu == 2.5:
        out = (1.0 + x + x * x / 3.0) * np.exp(-x)
    else:
        with np.errstate(invalid="ignore", divide="ignore"):
            out = (2.0 ** (1.0 - nu) / scipy.special.gamma(nu)
                   * x ** nu * scipy.special.kv(nu, x))
        out[x == 0.0] = 1.0
    out *= variance
    return out


def covariance(a: np.ndarray, b: np.ndarray, theta, nu: float, *,
               jitter: float = 0.0, out: np.ndarray | None = None) -> np.ndarray:
    """Sigma_ab in float64 for locations a (n_a, 2), b (n_b, 2), from the
    Euclidean distance of coordinate differences; `jitter` is added to
    the diagonal (give it only where a and b are one set)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    out = np.empty((a.shape[0], b.shape[0]), np.float64) if out is None else out
    variance, rng = float(theta[0]), float(theta[1])

    def rows(lo: int):
        hi = min(lo + ROWS_PER_CHUNK, a.shape[0])
        blk = out[lo:hi]
        np.subtract(a[lo:hi, 0:1], b[None, :, 0], out=blk)
        np.square(blk, out=blk)
        dy = np.subtract(a[lo:hi, 1:2], b[None, :, 1])
        np.square(dy, out=dy)
        blk += dy
        np.sqrt(blk, out=blk)
        blk[...] = matern(blk, variance, rng, nu)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(rows, range(0, a.shape[0], ROWS_PER_CHUNK)))
    if jitter:
        out[np.diag_indices(a.shape[0])] += jitter
    return out


def cholesky_inplace(c: np.ndarray) -> np.ndarray:
    """Factor the C-ordered symmetric `c` in place; returns U (F-ordered
    view of the same memory) with c = U^T U, i.e. c's lower triangle
    now holds L = U^T."""
    u, info = lapack.dpotrf(c.T, lower=0, overwrite_a=1, clean=0)
    if info != 0:
        raise np.linalg.LinAlgError(f"dpotrf info={info}: not positive "
                                    "definite in float64")
    return u


def lower_solve(u: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    """w = L^{-1} rhs for L = U^T (U from `cholesky_inplace`)."""
    w, info = lapack.dtrtrs(u, np.asarray(rhs, np.float64), lower=0, trans=1)
    if info != 0:
        raise np.linalg.LinAlgError(f"dtrtrs info={info}")
    return w


def lower_matvec(u: np.ndarray, x: np.ndarray) -> np.ndarray:
    """L x for L = U^T (U from `cholesky_inplace`)."""
    return blas.dtrmv(u, np.asarray(x, np.float64), lower=0, trans=1)
