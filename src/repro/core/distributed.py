"""Distributed mixed-precision panel Cholesky for the production mesh.

The banded-storage engine (panel_cholesky.py) is exact but its per-step
slices shrink by one tile per step -- GSPMD cannot keep shrinking,
misaligned slices sharded, so at n=512k it replicated the trailing matrix
(3.3 TB/chip, dry-run iteration 0).  This module reformulates the sweep
for SPMD:

  storage   : off  (n, n) lo dtype, sharded P("data", "model")
              band (p, t, nb, nb) hi dtype (the paper's DP band)
  per step k (unrolled, all shapes STATIC and mesh-aligned):
    potrf/band-TRSM on hi tiles (small gathers);
    lo TRSM on the FULL masked panel column  (row-masked, P("data"));
    hi sub-diagonal updates (exact, tiny);
    lo trailing update U = C C^T over the FULL matrix, applied under the
    trailing+off-band mask, sharded P("data", "model").

Full-width masked updates cost ~3x the useful n^3/3 FLOPs (every step
touches the whole matrix).  That is the *baseline* the §Perf hillclimb
attacks: `version="aligned"` shrinks the row range to the 16-tile-aligned
boundary (static per step, still shard-aligned), cutting the waste to
~1.5x; column pruning (v3) gets ~1.15x.  See EXPERIMENTS.md §Perf.

Named scopes (`jax.named_scope`, HLO `op_name` metadata only, as in
panel_cholesky.py) mirror the one-chip step's: `geostat_loglik_distributed`
holds `cov_build`, `factor` and `solve`, and each step of the `fori`
sweep's body is under `potrf`, `trsm_hi`, `trsm_lo`, `gather`,
`update_hi` or `update_lo`.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..covariance.matern import pairwise_distance
from ..models.sharding import constrain
from .precision import PrecisionPolicy, lo_matmul

_GEO_RULES_NOTE = """Logical axes used here (models/sharding.DEFAULT_RULES):
rows of the matrix -> "data", cols -> "model"."""


def _c_rows(x):
    return constrain(x, "geo_rows .")


def _c_mat(x):
    return constrain(x, "geo_rows geo_cols")


def build_covariance_distributed(locs, theta, *, nb: int,
                                 policy: PrecisionPolicy, nu_static=0.5,
                                 jitter: float = 1e-6):
    """(off (n,n) lo sharded, band (p,t,nb,nb) hi) from the Matern kernel.

    Distances are coordinate differences (`pairwise_distance`, as on the
    one-chip path), fused elementwise into the sharded (n, n) build.  The
    MXU form |a|^2+|b|^2-2ab^T cancels for near neighbours, and a TPU runs
    that fp32 matmul as one bf16 pass by default: the covariance it built
    was indefinite on a v5e (NaN likelihood at n = 16384).
    """
    n = locs.shape[0]
    p = n // nb
    t = min(policy.diag_thick, p)
    hi = policy.hi
    lo = policy.lo if policy.mode != "full" else policy.hi
    theta1, theta2 = theta[0], theta[1]

    locs_hi = locs.astype(hi)  # coord precision follows the band tier

    def _corr(r):
        x = r / theta2
        if nu_static == 0.5:
            c = jnp.exp(-x)
        elif nu_static == 1.5:
            c = (1.0 + x) * jnp.exp(-x)
        elif nu_static == 2.5:
            c = (1.0 + x + x * x / 3.0) * jnp.exp(-x)
        else:
            raise ValueError("distributed cov-gen uses half-integer nu")
        return theta1 * jnp.where(r == 0.0, 1.0, c)

    cov = _corr(_c_mat(pairwise_distance(locs_hi, locs_hi)))

    # off-band lower storage: band region + upper triangle zeroed so the
    # solve can use unmasked column matvecs
    ii = jnp.repeat(jnp.arange(p), nb)
    off_mask = (ii[:, None] - ii[None, :]) >= t
    off = _c_mat(jnp.where(off_mask, cov, 0.0).astype(lo))

    # hi band tiles built DIRECTLY from locations (slicing the sharded
    # (n, n) cov into 512 tiles gathered ~137 GB replicated stacks --
    # dry-run iteration D9b); the vmapped per-diagonal build stays local
    locs_t = locs_hi.reshape(p, nb, 2)

    def tile_cov(la, lb):
        return _corr(pairwise_distance(la, lb))

    band_cols = []
    for d in range(t):
        blk = jax.vmap(tile_cov)(locs_t[d:], locs_t[:p - d]).astype(hi)
        if d > 0:
            blk = jnp.concatenate(
                [jnp.zeros((d, nb, nb), hi), blk], axis=0)
        band_cols.append(blk)
    band = jnp.stack(band_cols, axis=1)
    band = band.at[:, 0].add(jitter * jnp.eye(nb, dtype=hi)[None])
    # shard the band storage: rows over data, tile rows over model
    band = constrain(band, "geo_rows . geo_cols .")
    return off, band


def panel_cholesky_distributed(off, band, policy: PrecisionPolicy, *,
                               version: str = "masked_full",
                               align: int = 16):
    """Factor in place; returns (off, band) with L in the same layout.

    version:
      masked_full : p unrolled full-width masked steps (v1; ~3x FLOP waste)
      aligned     : rows pruned to 16-tile-aligned boundaries (~1.5x waste;
                    shapes differ per step => must stay unrolled)
      fori        : masked_full inside ONE lax.fori_loop body -- identical
                    numerics/FLOPs, but the (off, band) carry is buffer-
                    aliased so peak memory stops scaling with p, and the
                    compile is one body instead of p (§Perf G5)
    """
    if version == "fori":
        return _panel_cholesky_fori(off, band, policy)
    p, t, nb, _ = band.shape
    n = p * nb
    hi = policy.hi
    lo = off.dtype
    row_tile = np.arange(p)

    for k in range(p):
        lkk = jnp.linalg.cholesky(band[k, 0])
        band = band.at[k, 0].set(lkk)
        lkk_lo = lkk.astype(lo)
        m_t = p - k - 1
        if m_t == 0:
            break

        # hi band-panel TRSMs (exact tiles)
        n_band_panel = min(t - 1, m_t)
        for d in range(1, n_band_panel + 1):
            upd = solve_triangular(lkk, band[k + d, d].T, lower=True).T
            band = band.at[k + d, d].set(upd)

        # lo panel TRSM over the full masked column (rows >= k+t)
        col = _c_rows(off[:, k * nb:(k + 1) * nb].astype(policy.solve_dtype))
        sol = solve_triangular(lkk_lo.astype(policy.solve_dtype), col.T,
                               lower=True).T
        row_mask = jnp.repeat(row_tile >= k + t, nb)[:, None]
        col_new = jnp.where(row_mask, sol, col).astype(lo)
        off = off.at[:, k * nb:(k + 1) * nb].set(_c_rows(col_new))

        # assemble the full panel column in lo: band rows + off rows
        c_band_rows = []
        for d in range(1, n_band_panel + 1):
            c_band_rows.append(((k + d), band[k + d, d].astype(lo)))
        c_lo = jnp.where(row_mask, col_new, 0.0)
        for idx, tile in c_band_rows:
            c_lo = c_lo.at[idx * nb:(idx + 1) * nb].set(tile)
        c_lo = _c_rows(c_lo)                       # (n, nb), rows <= k zero

        # hi sub-diagonal updates (dsyrk/dgemm band), ROLL-aligned: slicing
        # c_t at (k+d)-offsets is mesh-misaligned and made GSPMD gather
        # 17 GiB operands per (k,d) pair (iteration D9b); jnp.roll keeps
        # every operand full-width and sharded.  c_t rows <= k are zero, so
        # sub-k products vanish on their own; only roll wraparound needs a
        # mask.
        c_t = c_lo.reshape(p, nb, nb).astype(hi)
        c_t = constrain(c_t, "geo_rows geo_cols .")
        for d in range(0, min(t, m_t)):
            shifted = jnp.roll(c_t, d, axis=0) if d else c_t
            upd = jnp.einsum("iab,icb->iac", c_t, shifted,
                             preferred_element_type=hi)
            wrap_ok = (np.arange(p) >= d)[:, None, None]
            band = band.at[:, d].add(-jnp.where(wrap_ok, upd, 0.0))

        # lo off-band trailing update, full-width masked (v1) or row-aligned
        if version == "aligned":
            start_tile = ((k + 1 + align - 1) // align) * align
            start = min(start_tile * nb, n)
            u_rows = c_lo[start:]
            fr_lo = max(start - align * nb, 0)
            fringe = c_lo[fr_lo:start] if start > 0 else c_lo[:0]
            pieces = []
            if fringe.shape[0]:
                pieces.append((fr_lo, fringe))
            if u_rows.shape[0]:
                pieces.append((start, u_rows))
        else:
            pieces = [(0, c_lo)]
        for row0, c_rows in pieces:
            if c_rows.shape[0] == 0:
                continue
            u = lo_matmul(c_rows, c_lo.T, policy)  # (rows, n)
            u = constrain(u, "geo_rows geo_cols")
            rows_idx = row_tile[row0 // nb: row0 // nb + c_rows.shape[0] // nb]
            ii = jnp.repeat(jnp.asarray(rows_idx), nb)[:, None]
            jj = jnp.repeat(row_tile, nb)[None, :]
            mask = (ii - jj >= t) & (jj > k) & (ii > k)
            blk = off[row0:row0 + c_rows.shape[0]]
            off = off.at[row0:row0 + c_rows.shape[0]].set(
                jnp.where(mask, (blk - u.astype(lo)), blk))
    return off, band


def _c_r2(x):
    # fori-path sharding: rows 2-D (data x model), cols unsharded --
    # traced-offset column slices cannot cross a sharded dim
    return constrain(x, "geo_rows2d .")


def _panel_cholesky_fori(off, band, policy: PrecisionPolicy):
    """masked_full sweep as a single fori_loop body (all shapes static in
    k; masks/slices use the traced k).  See panel_cholesky_distributed."""
    p, t, nb, _ = band.shape
    n = p * nb
    hi = policy.hi
    lo = off.dtype
    row_tile = jnp.arange(p)
    ii = jnp.repeat(row_tile, nb)
    off = _c_r2(off)

    def step(k, carry):
        off, band = carry
        with jax.named_scope("potrf"):
            lkk = jnp.linalg.cholesky(band[k, 0])
            band = band.at[k, 0].set(lkk)
            lkk_lo = lkk.astype(lo)

        # hi band-panel TRSMs (traced index, clamped + validity-masked)
        with jax.named_scope("trsm_hi"):
            for d in range(1, t):
                idx = jnp.minimum(k + d, p - 1)
                tile = band[idx, d]
                upd = solve_triangular(lkk, tile.T, lower=True).T
                valid = (k + d) < p
                band = band.at[idx, d].set(jnp.where(valid, upd, tile))

        # lo panel TRSM over the full masked column
        with jax.named_scope("trsm_lo"):
            col = jax.lax.dynamic_slice(off, (0, k * nb), (n, nb))
            col = _c_r2(col.astype(policy.solve_dtype))
            sol = solve_triangular(lkk_lo.astype(policy.solve_dtype), col.T,
                                   lower=True).T
            row_mask = (ii >= k + t)[:, None]
            col_new = jnp.where(row_mask, sol, col).astype(lo)
            off = jax.lax.dynamic_update_slice(off, _c_r2(col_new),
                                               (0, k * nb))

        # assemble panel column: off rows (>= k+t) + hi band rows
        with jax.named_scope("gather"):
            c_lo = jnp.where(row_mask, col_new, 0.0)
            for d in range(1, t):
                idx = jnp.minimum(k + d, p - 1)
                cur = jax.lax.dynamic_slice(c_lo, (idx * nb, 0), (nb, nb))
                tile = jnp.where((k + d) < p, band[idx, d].astype(lo), cur)
                c_lo = jax.lax.dynamic_update_slice(c_lo, tile, (idx * nb, 0))
            c_lo = _c_r2(c_lo)                   # rows <= k are zero

        # hi sub-diagonal updates, roll-aligned (see unrolled variant)
        with jax.named_scope("update_hi"):
            c_t = constrain(c_lo.reshape(p, nb, nb).astype(hi),
                            "geo_rows geo_cols .")
            for d in range(t):
                shifted = jnp.roll(c_t, d, axis=0) if d else c_t
                upd = jnp.einsum("iab,icb->iac", c_t, shifted,
                                 preferred_element_type=hi)
                wrap_ok = (row_tile >= d)[:, None, None]
                band = band.at[:, d].add(-jnp.where(wrap_ok, upd, 0.0))

        # lo off-band trailing update, full-width masked
        with jax.named_scope("update_lo"):
            u = lo_matmul(c_lo, c_lo.T, policy)
            u = _c_r2(u)
            mask = ((ii[:, None] - ii[None, :] >= t)
                    & (ii[None, :] > k) & (ii[:, None] > k))
            off = _c_r2(jnp.where(mask, (off - u.astype(lo)), off))
        return off, band

    return jax.lax.fori_loop(0, p, step, (off, band))


def loglik_distributed(off, band, z, t: int):
    """Blocked forward solve + logdet on the distributed layout.

    COLUMN-wise substitution: after solving block j, its contribution is
    pushed into the running residual with one (n, nb) column matvec --
    column slices keep their row sharding, unlike the row-strip variant
    whose per-step (nb, j*nb) gathers summed to ~256 GB/chip at n=512k
    (dry-run iteration 2).  fori_loop body: the unrolled variant kept
    p live copies of the (n, nb) fp32 columns (§Perf G5)."""
    p, _, nb, _ = band.shape
    n = p * nb
    hi = band.dtype
    off = _c_r2(off)   # traced col slices below: cols must stay unsharded

    def step(j, carry):
        acc, w, logdet = carry
        rhs = jax.lax.dynamic_slice(acc, (j * nb, 0), (nb, 1))[:, 0]
        for d in range(1, t):
            idx = jnp.maximum(j - d, 0)
            wd = jax.lax.dynamic_slice(w, (idx * nb,), (nb,))
            contrib = band[j, d] @ wd
            rhs = rhs - jnp.where((j - d) >= 0, contrib, 0.0)
        ljj = band[j, 0]
        w_j = solve_triangular(ljj, rhs, lower=True)
        w = jax.lax.dynamic_update_slice(w, w_j, (j * nb,))
        logdet = logdet + jnp.sum(jnp.log(jnp.diagonal(ljj)))
        col = jax.lax.dynamic_slice(off, (0, j * nb), (n, nb)).astype(hi)
        acc = _c_r2(acc - col @ w_j[:, None])     # band rows of col are 0
        return acc, w, logdet

    acc0 = _c_r2(z.astype(hi)[:, None])
    _, w, logdet = jax.lax.fori_loop(
        0, p, step, (acc0, jnp.zeros((n,), hi), jnp.zeros((), hi)))
    return (-0.5 * n * jnp.log(2.0 * jnp.pi) - logdet
            - 0.5 * jnp.sum(w * w))


def geostat_loglik_distributed(locs, z, theta, *, nb: int,
                               policy: PrecisionPolicy, nu_static=0.5,
                               jitter: float = 1e-6,
                               version: str = "masked_full"):
    """One full MLE likelihood evaluation, SPMD-shardable end to end;
    `jitter` is added to the covariance's diagonal."""
    with jax.named_scope("geostat_loglik_distributed"):
        with jax.named_scope("cov_build"):
            off, band = build_covariance_distributed(
                locs, theta, nb=nb, policy=policy, nu_static=nu_static,
                jitter=jitter)
        t = min(policy.diag_thick, band.shape[0])
        with jax.named_scope("factor"):
            off, band = panel_cholesky_distributed(off, band, policy,
                                                   version=version)
        with jax.named_scope("solve"):
            return loglik_distributed(off, band, z, t)
