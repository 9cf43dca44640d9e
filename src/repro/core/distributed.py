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


def _c_r2(x):
    # fori-path sharding: rows 2-D (data x model), cols unsharded --
    # traced-offset column slices cannot cross a sharded dim
    return constrain(x, "geo_rows2d .")


def _c_c2(x):
    # the transpose of `_c_r2`: cols over both mesh axes, rows whole
    return constrain(x, ". geo_rows2d")


def _c_tiles(x):
    # stacks of (nb, nb) tiles: each tile's rows over both mesh axes, the
    # stack dims whole on every chip, so a tile read at a traced index
    # is a local slice
    return constrain(x, ". " * (x.ndim - 2) + "geo_rows2d .")


def _update_band(band, c_lo, n_diag: int):
    """band[i, d] -= C_i C_{i-d}^T for d < n_diag, C_i the (nb, nb) tiles
    of the (n, nb) lo panel column C: the hi sub-diagonal updates
    (dsyrk/dgemm band) of the `fori` sweep.  The panel's rows <= k are
    zero, so sub-k products vanish on their own.

    The panel becomes whole on every chip in the lo dtype and is cast to
    the band's dtype there.  The band's tile index is whole on every chip
    (`_c_tiles`), so the shifted operand is a local copy of the chip's own
    rows: each chip updates the band rows it holds, from its rows of
    C_{j+d} against the whole C_j.
    """
    p, _, nb, _ = band.shape
    whole = constrain(c_lo.reshape(p, nb, nb), ". . .").astype(band.dtype)
    rows = _c_tiles(whole)
    for d in range(n_diag):
        left = jnp.roll(rows, -d, axis=0) if d else rows
        upd = jnp.einsum("iab,icb->iac", left, whole,
                         preferred_element_type=band.dtype)
        band = band.at[d:, d].add(-upd[:p - d])
    return band


def build_covariance_distributed(locs, theta, *, nb: int,
                                 policy: PrecisionPolicy, nu_static=0.5,
                                 jitter: float = 1e-6,
                                 version: str = "masked_full"):
    """(off (n,n) lo sharded, band (p,t,nb,nb) hi) from the Matern kernel,
    laid out as `panel_cholesky_distributed`'s `version` takes them.

    The unrolled sweeps take off as the lower off-band storage, split over
    both mesh axes, and the band split by tile index over "data".  The
    `fori` sweep reads tiles at a traced step index, so it takes off
    transposed (the upper storage; Sigma is symmetric), its columns split
    over the whole mesh (`_c_c2`), and the band with each tile's rows
    split over the whole mesh (`_c_tiles`): a tile at any step is local.

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

    fori = version == "fori"
    c_off = _c_c2 if fori else _c_mat
    cov = _corr(c_off(pairwise_distance(locs_hi, locs_hi)))

    # off-band lower storage: band region + upper triangle zeroed so the
    # solve can use unmasked column matvecs
    ii = jnp.repeat(jnp.arange(p), nb)
    off_mask = (ii[:, None] - ii[None, :]) >= t
    off = c_off(jnp.where(off_mask.T if fori else off_mask, cov,
                          0.0).astype(lo))

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
    if fori:
        return off, _c_tiles(band)
    # shard the band storage: rows over data, tile rows over model
    return off, constrain(band, "geo_rows . geo_cols .")


def panel_cholesky_distributed(off, band, policy: PrecisionPolicy, *,
                               version: str = "masked_full",
                               align: int = 16):
    """Factor in place; returns (off, band) with L in the layout
    `build_covariance_distributed` gives for the same `version`: off
    holds L's lower storage, or L^T for `fori`.

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


def _panel_cholesky_fori(off_t, band, policy: PrecisionPolicy):
    """masked_full sweep as a single fori_loop body (all shapes static in
    k; masks/slices use the traced k).  See panel_cholesky_distributed.

    Takes and returns off transposed, L^T split by columns: step k's
    panel column of L is a row block of L^T, read, solved and written
    back in the layout the triangular solve takes (its right-hand sides
    along the minor dimension).  Held as L, the TPU compiler copied each
    chip's whole share of the storage into that layout every step."""
    p, t, nb, _ = band.shape
    n = p * nb
    lo = off_t.dtype
    ii = jnp.repeat(jnp.arange(p), nb)

    def step(k, carry):
        off_t, band = carry
        # band tiles at the traced k are local slices (`_c_tiles`); the
        # diagonal tile alone is gathered, and factored on every chip
        with jax.named_scope("potrf"):
            lkk = constrain(jnp.linalg.cholesky(constrain(band[k, 0], ". .")),
                            ". .")
            band = band.at[k, 0].set(lkk)
            lkk_lo = lkk.astype(lo)

        # hi band-panel TRSMs (traced index, clamped + validity-masked),
        # on the chip that holds each row of the tile
        with jax.named_scope("trsm_hi"):
            panel = []
            for d in range(1, t):
                idx = jnp.minimum(k + d, p - 1)
                tile = band[idx, d]
                upd_t = solve_triangular(lkk, tile.T, lower=True)
                valid = (k + d) < p
                band = band.at[idx, d].set(jnp.where(valid, upd_t.T, tile))
                panel.append(upd_t)

        # lo panel TRSM over the full masked column (a row block of L^T)
        with jax.named_scope("trsm_lo"):
            row = jax.lax.dynamic_slice(off_t, (k * nb, 0), (nb, n))
            row = _c_c2(row.astype(policy.solve_dtype))
            sol = solve_triangular(lkk_lo.astype(policy.solve_dtype), row,
                                   lower=True)
            col_mask = (ii >= k + t)[None, :]
            row_new = jnp.where(col_mask, sol, row).astype(lo)
            off_t = jax.lax.dynamic_update_slice(off_t, _c_c2(row_new),
                                                 (k * nb, 0))

        # assemble the panel C^T, split by columns as L^T is: off columns
        # (>= k+t) + the band tiles trsm_hi solved, each put in by a mask
        # (a write at a traced column made GSPMD gather the whole panel)
        with jax.named_scope("gather"):
            c_t = jnp.where(col_mask, row_new, 0.0)
            for d, upd_t in enumerate(panel, start=1):
                tiles = jnp.tile(constrain(upd_t.astype(lo), ". ."), (1, p))
                c_t = jnp.where((ii == k + d)[None, :], tiles, c_t)
            c_t = _c_c2(c_t)                     # cols <= k are zero

        with jax.named_scope("update_hi"):
            c_t = constrain(c_t, ". .")          # both updates read all of it
            band = _update_band(band, c_t.T, t)

        # lo off-band trailing update, full-width masked, transposed
        with jax.named_scope("update_lo"):
            u_t = _c_c2(lo_matmul(c_t.T, c_t, policy))
            mask_t = ((ii[None, :] - ii[:, None] >= t)
                      & (ii[:, None] > k) & (ii[None, :] > k))
            off_t = _c_c2(jnp.where(mask_t, (off_t - u_t.astype(lo)), off_t))
        return off_t, band

    return jax.lax.fori_loop(0, p, step, (_c_c2(off_t), _c_tiles(band)))


def loglik_distributed(off_t, band, z, t: int):
    """Blocked forward solve + logdet on the distributed layout, from L^T
    (`off_t`, the off-band storage as the `fori` sweep holds it).

    COLUMN-wise substitution: after solving block j, its contribution is
    pushed into the running residual with one (n, nb) column matvec --
    column slices keep their row sharding, unlike the row-strip variant
    whose per-step (nb, j*nb) gathers summed to ~256 GB/chip at n=512k
    (dry-run iteration 2).  Column j of L is row block j of L^T, a local
    slice of `. geo_rows2d`; the band's tiles at the traced j are local
    slices of `_c_tiles`.  fori_loop body: the unrolled variant kept
    p live copies of the (n, nb) fp32 columns (§Perf G5)."""
    p, _, nb, _ = band.shape
    n = p * nb
    hi = band.dtype
    off_t, band = _c_c2(off_t), _c_tiles(band)

    def step(j, carry):
        acc, w, logdet = carry
        rhs = jax.lax.dynamic_slice(acc, (j * nb, 0), (nb, 1))[:, 0]
        for d in range(1, t):
            idx = jnp.maximum(j - d, 0)
            wd = jax.lax.dynamic_slice(w, (idx * nb,), (nb,))
            contrib = band[j, d] @ wd
            rhs = rhs - jnp.where((j - d) >= 0, contrib, 0.0)
        ljj = constrain(band[j, 0], ". .")       # one tile, whole
        w_j = solve_triangular(ljj, constrain(rhs, "."), lower=True)
        w = jax.lax.dynamic_update_slice(w, w_j, (j * nb,))
        logdet = logdet + jnp.sum(jnp.log(jnp.diagonal(ljj)))
        col_t = jax.lax.dynamic_slice(off_t, (j * nb, 0), (nb, n)).astype(hi)
        acc = _c_r2(acc - (w_j @ col_t)[:, None])  # band rows of col are 0
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
                jitter=jitter, version=version)
        t = min(policy.diag_thick, band.shape[0])
        with jax.named_scope("factor"):
            off, band = panel_cholesky_distributed(off, band, policy,
                                                   version=version)
        with jax.named_scope("solve"):
            # the unrolled sweeps hold L, the solve reads L^T
            off_t = off if version == "fori" else off.T
            return loglik_distributed(off_t, band, z, t)
