"""Maximum likelihood estimation drivers (paper Sec. IV-C).

The paper optimizes the likelihood with NLopt's derivative-free BOBYQA; we
provide (a) a derivative-free Nelder-Mead in log-parameter space (host loop
around a jitted likelihood -- mirrors the paper's setup, robust to the
mixed-precision likelihood's slight non-smoothness) and (b) a gradient path
(Adam on -loglik via jax.grad through the tile factorization) as the
beyond-paper alternative.

Counts likelihood evaluations/iterations so the paper's "MP needs more
iterations on strongly-correlated data" observation can be reproduced.

Both drivers can run on the batched evaluation engine
(`core/batch_engine.py`): `fit_mle_grid` is a batched iterative grid search
(every refinement level is ONE device call over the whole candidate grid),
and `neldermead`/`fit_mle` accept a batched function that evaluates the
initial simplex, the speculative reflection/expansion/contraction triple,
and shrink steps in single batched calls -- the vmap analogue of the
parallel likelihood evaluations in the ExaGeoStat follow-up work
(arXiv:1804.09137).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs


@dataclass
class MLEResult:
    theta: np.ndarray
    loglik: float
    n_evals: int
    n_iters: int
    converged: bool
    history: list


def neldermead(fn: Callable, x0, *, xtol: float = 1e-3, ftol: float = 1e-6,
               max_iters: int = 200, scale: float = 0.25,
               fn_batch: Callable | None = None):
    """Minimize fn (host-side NM; fn is typically a jitted device function).

    Works in the unconstrained space the caller provides (we use log-theta).
    Returns (x_best, f_best, n_evals, n_iters, converged, history).

    fn_batch: optional (B, d) -> (B,) batched version of fn.  When given,
    the initial simplex and shrink steps run as single batched calls, and
    each iteration *speculatively* evaluates the reflection, expansion and
    contraction candidates together in one batched call.  That spends 3
    evals/iteration where the sequential path often needs only 1, so it
    pays off when per-eval dispatch/host-sync overhead dominates (small-n
    problems, the regime bench_batched_mle.py measures); when the O(n^3)
    factorization itself dominates, the speculative work can cost up to
    ~3x the FLOPs -- leave fn_batch unset there.  The accepted point is
    identical to the sequential algorithm's either way.

    Each iteration is one `mle.iter` telemetry span; the initial simplex
    is evaluated before the first.
    """
    x0 = np.asarray(x0, dtype=np.float64)
    d = x0.size
    pts = [x0] + [x0 + scale * np.eye(d)[i] for i in range(d)]
    simplex = np.stack(pts)
    if fn_batch is not None:
        fvals = np.asarray(fn_batch(simplex), dtype=np.float64)
    else:
        fvals = np.array([float(fn(p)) for p in simplex])
    n_evals = d + 1
    history = []

    alpha, gamma, rho, sigma = 1.0, 2.0, 0.5, 0.5
    converged = False
    it = 0
    for it in range(1, max_iters + 1):
        with obs.span("mle.iter"):
            order = np.argsort(fvals)
            simplex, fvals = simplex[order], fvals[order]
            history.append((simplex[0].copy(), fvals[0]))
            if (np.max(np.abs(simplex[1:] - simplex[0])) < xtol
                    and np.max(np.abs(fvals[1:] - fvals[0])) < ftol):
                converged = True
                break
            centroid = simplex[:-1].mean(axis=0)
            xr = centroid + alpha * (centroid - simplex[-1])
            xe = centroid + gamma * (xr - centroid)
            xc = centroid + rho * (simplex[-1] - centroid)
            if fn_batch is not None:
                fr, fe, fc = np.asarray(
                    fn_batch(np.stack([xr, xe, xc])), dtype=np.float64)
                n_evals += 3
            else:
                fr = float(fn(xr)); n_evals += 1
                fe = fc = None
            if fvals[0] <= fr < fvals[-2]:
                simplex[-1], fvals[-1] = xr, fr
            elif fr < fvals[0]:
                if fe is None:
                    fe = float(fn(xe)); n_evals += 1
                if fe < fr:
                    simplex[-1], fvals[-1] = xe, fe
                else:
                    simplex[-1], fvals[-1] = xr, fr
            else:
                if fc is None:
                    fc = float(fn(xc)); n_evals += 1
                if fc < fvals[-1]:
                    simplex[-1], fvals[-1] = xc, fc
                else:  # shrink
                    if fn_batch is not None:
                        simplex[1:] = simplex[0] + sigma * (simplex[1:]
                                                            - simplex[0])
                        fvals[1:] = np.asarray(fn_batch(simplex[1:]),
                                               dtype=np.float64)
                        n_evals += d
                    else:
                        for i in range(1, d + 1):
                            simplex[i] = simplex[0] + sigma * (simplex[i]
                                                               - simplex[0])
                            fvals[i] = float(fn(simplex[i])); n_evals += 1
    order = np.argsort(fvals)
    return simplex[order][0], fvals[order][0], n_evals, it, converged, history


def fit_mle(loglik_fn: Callable, theta0, *, xtol: float = 1e-3,
            max_iters: int = 200, jit: bool = True,
            batched_loglik_fn: Callable | None = None) -> MLEResult:
    """Derivative-free MLE: maximize loglik over positive theta.

    theta0: initial (theta1, theta2, theta3) (or 2-vector for the profiled
    likelihood).  Optimization runs on log(theta) so positivity is free.

    batched_loglik_fn: optional (B, d) thetas -> (B,) log-likelihoods (e.g.
    `BatchEngine.loglik` or a slice-wrapper around it); enables the
    speculative batched Nelder-Mead (see `neldermead`).  When given, every
    NM evaluation goes through it, so loglik_fn may be None -- the batched
    function alone fully specifies the model.
    """
    theta0 = np.asarray(theta0, dtype=np.float64)

    neg_batch = None
    if batched_loglik_fn is not None:
        def neg_batch(xs):
            with obs.span("mle.eval_batch"):
                v = np.asarray(batched_loglik_fn(jnp.exp(jnp.asarray(xs))),
                               dtype=np.float64)
            return np.where(np.isfinite(v), -v, 1e10)

    if loglik_fn is None:
        if neg_batch is None:
            raise ValueError("need loglik_fn or batched_loglik_fn")

        def neg_ll_log(x):  # scalar fallback derived from the batched fn
            return float(neg_batch(np.asarray(x)[None])[0])
    else:
        ll = jax.jit(loglik_fn) if jit else loglik_fn

        def neg_ll_log(x):
            with obs.span("mle.theta"):
                theta = jnp.exp(jnp.asarray(x))
            with obs.span("mle.eval"):
                v = float(ll(theta))
            return 1e10 if not np.isfinite(v) else -v

    with obs.span("mle.fit", driver="neldermead",
                  batched=neg_batch is not None):
        x, f, n_evals, n_iters, conv, hist = neldermead(
            neg_ll_log, np.log(theta0), xtol=xtol, max_iters=max_iters,
            fn_batch=neg_batch)
    obs.inc("mle.fits")
    return MLEResult(theta=np.exp(x), loglik=-f, n_evals=n_evals,
                     n_iters=n_iters, converged=conv,
                     history=[(np.exp(h[0]), -h[1]) for h in hist])


def fit_mle_grid(batched_loglik_fn: Callable, bounds, *, num: int = 12,
                 refine: int = 3, shrink: float = 0.4) -> MLEResult:
    """Batched iterative grid search: maximize loglik over positive theta.

    Every refinement level evaluates the FULL `num**d` candidate grid in one
    batched engine call (`batched_loglik_fn`: (B, d) -> (B,)), then recenters
    a log-space grid of `shrink` x the previous span on the incumbent.  This
    is the throughput-oriented estimation driver: `refine` device
    round-trips total (one per level) instead of one per candidate.

    bounds: sequence of (lo, hi) per parameter, in theta space (positive);
    the grid is laid out in log space like the NM driver.
    """
    bounds = np.asarray(bounds, dtype=np.float64)
    if bounds.ndim != 2 or bounds.shape[1] != 2 or np.any(bounds <= 0.0):
        raise ValueError("bounds must be (d, 2) with positive entries")
    d = bounds.shape[0]
    lo0, hi0 = np.log(bounds[:, 0]), np.log(bounds[:, 1])
    lo, hi = lo0.copy(), hi0.copy()
    best_x, best_f = None, -np.inf
    n_evals = 0
    history = []
    with obs.span("mle.fit", driver="grid", levels=refine):
        for _ in range(refine):
            axes = [np.linspace(lo[i], hi[i], num) for i in range(d)]
            mesh = np.stack(np.meshgrid(*axes, indexing="ij"),
                            axis=-1).reshape(-1, d)
            with obs.span("mle.eval_batch"):
                ll = np.asarray(
                    batched_loglik_fn(jnp.exp(jnp.asarray(mesh))),
                    dtype=np.float64)
            ll = np.where(np.isfinite(ll), ll, -np.inf)
            n_evals += mesh.shape[0]
            k = int(np.argmax(ll))
            if ll[k] > best_f:
                best_f, best_x = float(ll[k]), mesh[k].copy()
            if best_x is None:
                raise ValueError(
                    "fit_mle_grid: every candidate log-likelihood in the "
                    f"first {mesh.shape[0]}-point grid level was non-finite; "
                    "widen or shift `bounds` (the covariance is likely not "
                    "SPD there)")
            history.append((np.exp(best_x), best_f))
            # recenter on the incumbent, clamped so refined grids (and hence
            # the returned theta) never leave the caller's bounds box
            span = (hi - lo) * shrink
            lo = np.clip(best_x - span / 2.0, lo0, hi0)
            hi = np.clip(best_x + span / 2.0, lo0, hi0)
    obs.inc("mle.fits")
    return MLEResult(theta=np.exp(best_x), loglik=best_f, n_evals=n_evals,
                     n_iters=refine, converged=True, history=history)


def fit_mle_adam(loglik_fn: Callable, theta0, *, steps: int = 150,
                 lr: float = 0.05) -> MLEResult:
    """Gradient MLE: Adam on -loglik(exp(x)) via autodiff through the
    factorization (beyond-paper path; requires a differentiable policy)."""
    x0 = jnp.log(jnp.asarray(theta0, dtype=jnp.float32))

    neg = lambda x: -loglik_fn(jnp.exp(x))
    grad_fn = jax.jit(jax.value_and_grad(neg))

    @jax.jit
    def update(x, m, v, i):
        f, g = jax.value_and_grad(neg)(x)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mhat = m / (1 - 0.9 ** i)
        vhat = v / (1 - 0.999 ** i)
        x = x - lr * mhat / (jnp.sqrt(vhat) + 1e-8)
        return x, m, v, f

    x, m, v = x0, jnp.zeros_like(x0), jnp.zeros_like(x0)
    f = jnp.inf
    history = []
    for i in range(1, steps + 1):
        x, m, v, f = update(x, m, v, i)
        if i % 10 == 0:
            history.append((np.exp(np.asarray(x)), -float(f)))
    f_final, _ = grad_fn(x)
    return MLEResult(theta=np.exp(np.asarray(x)), loglik=-float(f_final),
                     n_evals=steps, n_iters=steps, converged=True,
                     history=history)
