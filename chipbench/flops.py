"""Operations that the algorithm needs, counted from its sizes.

Counted from n alone, never from what an implementation issues: a square
update that computes twice the needed products, or a change of tier
arithmetic, leaves these counts as they are.
"""

from __future__ import annotations

# distance (2 differences, 2 squares, 1 add, 1 sqrt), scale, exp, variance
FLOPS_PER_COVARIANCE_ENTRY = 9


def cholesky_flops(n: int) -> float:
    """Dense Cholesky of an n x n SPD matrix: n^3/3 + n^2/2 + n/6."""
    return n ** 3 / 3 + n ** 2 / 2 + n / 6


def forward_solve_flops(n: int) -> float:
    """Lower-triangular solve with one right-hand side: n^2."""
    return float(n) * n


def covariance_flops(n: int) -> float:
    """Matern (nu = 0.5) entries of the symmetric matrix's lower triangle."""
    return n * (n + 1) / 2 * FLOPS_PER_COVARIANCE_ENTRY


def loglik_flops(n: int) -> float:
    """One log-likelihood evaluation: covariance, factor, solve, dot."""
    return covariance_flops(n) + cholesky_flops(n) + forward_solve_flops(n) + 2 * n
