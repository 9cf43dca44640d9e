"""TPU-native mixed-precision panel Cholesky (the performance path).

This is the hardware adaptation of paper Algorithm 1 (see DESIGN.md §3):
instead of a StarPU task DAG over heterogeneous tiles, the factorization is
restructured into p statically-shaped, trace-time-unrolled panel steps over
a *split storage*:

  band : (p, t, nb, nb) in hi dtype -- band[i, d] = tile (i, i-d), i.e. the
         diag_thick tile sub-diagonals the paper keeps in double precision;
  off  : (p, p, nb, nb) in lo dtype -- tiles with i - j >= t (lower
         triangle), i.e. the single-precision region.  Storing these in lo
         is the TPU analogue of the paper keeping SP copies in the spare
         triangle: it halves their HBM/ICI bytes.

Per step k (all slices static because the loop is unrolled):
  1. potrf(band[k,0]) in hi                               (dpotrf)
  2. hi TRSM on the <= t-1 band panel tiles               (dtrsm)
     lo TRSM on the off panel tiles                       (strsm)
  3. hi batched sub-diagonal updates d = 0..t-1           (dsyrk/dgemm)
  4. lo GEMMs on the off-band tiles (i, j) with j + t <= i,
     the step's lower trapezoid                           (sgemm)

Step 4 (`off_update="chunked"`, the default) touches the trapezoid's
tiles alone, in place.  On a TPU, for bf16 tiles whose size is a
multiple of 128, it is one Pallas call per step
(`kernels/lo_trailing_update`); elsewhere one batched GEMM per tile
column.  `off_update="square"` computes the whole (m x m) product
C_lo C_lo^T and keeps the trapezoid under a static tile mask: ~3x the
trapezoid's FLOPs at p = 32, plus a transposed copy and a whole-block
select; `BatchEngine`, which vmaps the step, asks for it.

Everything is jnp (differentiable, GSPMD-shardable).  Numerics match the
faithful tile engine (tests assert allclose against tile_cholesky.py).

Named scopes (`jax.named_scope`, HLO `op_name` metadata only, DESIGN.md
§13) mark each phase inside the compiled program: `geostat_loglik_step`
holds `cov_build`, `factor` and `solve`, and inside the factorization
each step's work is under `potrf`, `trsm_hi`, `trsm_lo`, `gather`,
`update_hi` or `update_lo`.  The names are fixed, never per step, so a
profiler trace sums each phase.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..covariance.matern import matern_covariance
from ..kernels.lo_trailing_update.lo_trailing_update import (
    lo_trailing_update_pallas)
from .precision import PrecisionPolicy, lo_matmul


# ----------------------------------------------------------------------
# banded storage construction
# ----------------------------------------------------------------------

def build_banded_covariance(locs, theta, *, nb: int, policy: PrecisionPolicy,
                            nu_static=None, metric="euclidean", jitter=1e-6):
    """Matern covariance directly into (band, off) split storage.

    band[i, d] = Sigma tile (i, i-d) in hi; off[i, j] = tile (i, j) in lo
    (only i - j >= t is meaningful; the rest is zero).
    """
    locs = jnp.asarray(locs)
    n = locs.shape[0]
    assert n % nb == 0
    p = n // nb
    t = min(policy.diag_thick, p)
    hi, lo = policy.hi, (policy.lo if policy.mode != "full" else policy.hi)

    locs_t = locs.reshape(p, nb, locs.shape[-1])

    def tile_cov(la, lb):
        return matern_covariance(la, lb, theta, nu_static=nu_static, metric=metric)

    pair_cov = jax.vmap(tile_cov)

    # band sub-diagonals
    band_cols = []
    for d in range(t):
        blk = pair_cov(locs_t[d:], locs_t[:p - d]).astype(hi)   # (p-d, nb, nb)
        if d > 0:
            blk = jnp.concatenate(
                [jnp.zeros((d, nb, nb), dtype=hi), blk], axis=0)
        band_cols.append(blk)
    band = jnp.stack(band_cols, axis=1)                          # (p, t, nb, nb)
    eye = jnp.eye(nb, dtype=hi) * jitter
    band = band.at[:, 0].add(eye[None])

    # off-band tiles (full p x p grid; only i-j >= t used downstream)
    off = jax.vmap(lambda la: pair_cov(
        jnp.broadcast_to(la[None], (p,) + la.shape), locs_t))(locs_t)
    ii, jj = np.meshgrid(np.arange(p), np.arange(p), indexing="ij")
    off_mask = jnp.asarray((ii - jj) >= t)[:, :, None, None]
    off = jnp.where(off_mask, off, 0.0).astype(lo)               # (p, p, nb, nb)
    return band, off


def assemble_from_banded(band, off, t: int, dtype=None):
    """(band, off) -> dense lower-triangular (n, n) matrix in hi."""
    p, _, nb, _ = band.shape
    dtype = dtype or band.dtype
    n = p * nb
    out = jnp.zeros((n, n), dtype=dtype)
    for i in range(p):
        for d in range(min(i + 1, t)):
            j = i - d
            out = out.at[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].set(
                band[i, d].astype(dtype))
        for j in range(0, i - t + 1):
            out = out.at[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].set(
                off[i, j].astype(dtype))
    tri = jnp.tril(jnp.ones((n, n), dtype=bool))
    return jnp.where(tri, out, jnp.zeros((), dtype=dtype))


# ----------------------------------------------------------------------
# the factorization
# ----------------------------------------------------------------------

def _batched_trsm_right_lt(l, a, exec_dtype, out_dtype):
    """a[i] <- a[i] L^{-T} for a: (m, nb, nb)."""
    l = l.astype(exec_dtype)
    a = a.astype(exec_dtype)
    l = jnp.broadcast_to(l, a.shape[:-2] + l.shape[-2:])
    x = solve_triangular(l, jnp.swapaxes(a, -1, -2), lower=True)
    return jnp.swapaxes(x, -1, -2).astype(out_dtype)


def _lo_update_loop(c, off, *, k: int, t: int, policy: PrecisionPolicy):
    """Step k's lo update, one batched GEMM per target tile column j: only
    rows i >= j + t receive c[i] c[j]^T.  c[m] = tile (k+1+m, k)."""
    p, nb = off.shape[0], off.shape[-1]
    for j in range(k + 1, p - t):
        lhs = c[j + t - k - 1:]                 # tiles (j+t..p-1, k)
        rhs = c[j - k - 1]                      # tile (j, k)
        upd = lo_matmul(lhs, jnp.broadcast_to(
            rhs.T[None], (lhs.shape[0], nb, nb)), policy)
        off = off.at[j + t:, j].add(-upd.astype(off.dtype))
    return off


def _lo_update(c, off, *, k: int, t: int, policy: PrecisionPolicy):
    """Step k's lo update on its lower trapezoid, in place.

    On a TPU, bf16 tiles whose size is a multiple of 128 take the Pallas
    kernel, one call; every other platform, dtype or size takes the loop.
    The platform is chosen when the program is lowered, so a compile for
    a described TPU takes the kernel too.  Derivatives are the loop's.
    """
    p, nb = off.shape[0], off.shape[-1]
    if k + 1 + t > p - 1:                       # empty trapezoid
        return off
    loop = partial(_lo_update_loop, k=k, t=t, policy=policy)
    if off.dtype != jnp.bfloat16 or nb % 128:
        return loop(c, off)
    kernel = partial(lo_trailing_update_pallas, k=k, t=t,
                     accum_dtype=policy.accum_dtype, interpret=False)

    @jax.custom_jvp
    def update(c, off):
        return jax.lax.platform_dependent(c, off, tpu=kernel, default=loop)

    @update.defjvp
    def _(primals, tangents):
        return update(*primals), jax.jvp(loop, primals, tangents)[1]

    return update(c, off)


def panel_cholesky_banded(band, off, policy: PrecisionPolicy, *,
                          off_update: str = "chunked"):
    """Factor the banded-storage SPD matrix in place. Returns (band, off).

    off_update: "chunked" -- the lo update on each step's lower trapezoid
                             only (`_lo_update`: exact FLOPs, in place);
                "square"  -- one full m x m lo GEMM per step under a tile
                             mask (~3x the FLOPs at p = 32; vmappable).

    Each step's work is under one of the named scopes `potrf`, `trsm_hi`,
    `trsm_lo`, `gather`, `update_hi` and `update_lo`.
    """
    p, t, nb, _ = band.shape
    hi = policy.hi
    lo = off.dtype

    for k in range(p):
        with jax.named_scope("potrf"):
            lkk = jnp.linalg.cholesky(band[k, 0])
            band = band.at[k, 0].set(lkk)
            lkk_lo = lkk.astype(lo)

        m_t = p - k - 1
        if m_t == 0:
            break

        # --- panel TRSMs -------------------------------------------------
        n_band_panel = min(t - 1, m_t)
        with jax.named_scope("trsm_hi"):
            for d in range(1, n_band_panel + 1):  # dtrsm (hi), tiles (k+d, k)
                upd = _batched_trsm_right_lt(lkk, band[k + d, d][None],
                                             hi, hi)[0]
                band = band.at[k + d, d].set(upd)
        if k + t <= p - 1:                            # strsm (lo)
            with jax.named_scope("trsm_lo"):
                sol = _batched_trsm_right_lt(lkk_lo, off[k + t:, k],
                                             policy.solve_dtype, lo)
                off = off.at[k + t:, k].set(sol)

        # --- gather the factored panel column as hi tiles ----------------
        with jax.named_scope("gather"):
            parts = [band[k + d, d][None] for d in range(1, n_band_panel + 1)]
            if k + t <= p - 1:
                parts.append(off[k + t:, k].astype(hi))
            c_hi = jnp.concatenate(parts, axis=0)
        # c_hi[m] = tile (k+1+m, k), shape (m_t, nb, nb)

        # --- hi band updates: sub-diagonals d = 0..t-1 (dsyrk/dgemm) -----
        with jax.named_scope("update_hi"):
            for d in range(0, min(t, m_t)):
                lhs = c_hi[d:]
                rhs = c_hi[:m_t - d]
                upd = jnp.einsum("iab,icb->iac", lhs, rhs,
                                 preferred_element_type=hi)
                band = band.at[k + 1 + d:, d].add(-upd.astype(hi))

        # --- lo off-band update (sgemm) ----------------------------------
        with jax.named_scope("gather"):
            c_lo = c_hi.astype(lo).reshape(m_t * nb, nb)
        with jax.named_scope("update_lo"):
            if off_update == "square":
                ii, jj = np.meshgrid(np.arange(k + 1, p), np.arange(k + 1, p),
                                     indexing="ij")
                mask = jnp.asarray((ii - jj) >= t)[:, :, None, None]
                u = lo_matmul(c_lo, c_lo.T, policy)              # (m, m) lo
                u_t = u.reshape(m_t, nb, m_t, nb).transpose(0, 2, 1, 3)
                blk = off[k + 1:, k + 1:]
                off = off.at[k + 1:, k + 1:].set(
                    jnp.where(mask, (blk - u_t.astype(lo)), blk))
            elif off_update == "chunked":
                off = _lo_update(c_lo.reshape(m_t, nb, nb), off, k=k, t=t,
                                 policy=policy)
            else:
                raise ValueError(off_update)
    return band, off


# ----------------------------------------------------------------------
# solve / likelihood on banded storage
# ----------------------------------------------------------------------

def banded_forward_solve(band, off, z, t: int):
    """w = L^{-1} z via blocked forward substitution on split storage."""
    p, _, nb, _ = band.shape
    hi = band.dtype
    z_t = z.astype(hi).reshape(p, nb)
    ws = []
    for i in range(p):
        acc = z_t[i]
        for d in range(1, min(i + 1, t)):
            acc = acc - band[i, d] @ ws[i - d]
        if i - t >= 0:
            w_mat = jnp.stack(ws[:i - t + 1])            # (i-t+1, nb)
            acc = acc - jnp.einsum("jab,jb->a", off[i, :i - t + 1].astype(hi),
                                   w_mat)
        ws.append(solve_triangular(band[i, 0], acc, lower=True))
    return jnp.concatenate(ws)


def banded_loglik(band, off, z, t: int):
    """Gaussian log-likelihood (Eq. 2) from the factored banded storage."""
    p, _, nb, _ = band.shape
    n = p * nb
    diag = jnp.stack([jnp.diagonal(band[i, 0]) for i in range(p)])
    logdet_half = jnp.sum(jnp.log(diag))
    w = banded_forward_solve(band, off, z, t)
    return (-0.5 * n * jnp.log(2.0 * jnp.pi) - logdet_half
            - 0.5 * jnp.sum(w * w))


def geostat_loglik_step(locs, z, theta, *, nb: int, policy: PrecisionPolicy,
                        nu_static=None, metric="euclidean", jitter=1e-6,
                        off_update: str = "chunked"):
    """One full likelihood evaluation: cov-gen -> factor -> solve -> ll.

    This is the unit the paper benchmarks ("time per iteration") and the
    function the geostat dry-run lowers on the production mesh.
    """
    with jax.named_scope("geostat_loglik_step"):
        with jax.named_scope("cov_build"):
            band, off = build_banded_covariance(
                locs, theta, nb=nb, policy=policy, nu_static=nu_static,
                metric=metric, jitter=jitter)
        t = min(policy.diag_thick, band.shape[0])
        with jax.named_scope("factor"):
            band, off = panel_cholesky_banded(band, off, policy,
                                              off_update=off_update)
        with jax.named_scope("solve"):
            return banded_loglik(band, off, z, t)
