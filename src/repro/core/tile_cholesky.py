"""Mixed-precision tile Cholesky factorization -- paper Algorithm 1, faithful.

This module is the *numerical reference* implementation: a tile-by-tile,
trace-time-unrolled right-looking Cholesky in which every tile op runs in the
dtype Algorithm 1 prescribes:

  line  8  dpotrf   : diagonal tile, hi precision
  line  9  dlag2s   : hi->lo copy of the factored diagonal tile (tmp)
  line 12  dtrsm    : panel tile inside the band, hi
  line 14  strsm    : panel tile outside the band, lo (using the lo tmp tile)
  line 15  sconv2d  : lo->hi refresh of the hi copy (needed by dsyrk)
  line 19  dsyrk    : diagonal-tile update, ALWAYS hi (operands upcast)
  line 25  dgemm    : in-band trailing tile, hi
  line 27  sgemm    : off-band trailing tile, lo math AND lo storage
                      (off-band accumulation error compounds in lo exactly
                      as in the paper, where SP tiles live in the spare
                      triangle of the symmetric matrix)

Off-band tiles are *stored* in `policy.lo`; band tiles in `policy.hi`.
Unrolling is fine for the statistical studies (p <= ~40 tiles).  The
performance/distributed path lives in panel_cholesky.py.

Also implements the DST (Diagonal-Super-Tile / independent blocks)
covariance-tapering baseline of paper Sec. V-B.

Inside jit each tile op carries a fixed named scope (HLO `op_name`
metadata only, DESIGN.md §13): `convert` (the dlag2s storage casts and
the final assembly), `potrf`, `trsm_hi`, `trsm_lo`, `update_hi`
(dsyrk/dgemm) and `update_lo` (sgemm).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .. import obs
from .precision import PrecisionPolicy, lo_matmul


def _potrf(a, dtype):
    return jnp.linalg.cholesky(a.astype(dtype))


def _trsm_right_lt(l_kk, a_ik, exec_dtype, out_dtype):
    """A_ik <- A_ik * L_kk^{-T} executed in exec_dtype, stored as out_dtype."""
    l = l_kk.astype(exec_dtype)
    a = a_ik.astype(exec_dtype)
    x = solve_triangular(l, jnp.swapaxes(a, -1, -2), lower=True, trans=0)
    return jnp.swapaxes(x, -1, -2).astype(out_dtype)


def split_tiles(a, nb: int):
    """(..., n, n) -> dict[(i, j)] -> (..., nb, nb) lower-triangle tiles.

    Leading axes of `a` are treated as a batch of matrices.
    """
    n = a.shape[-1]
    assert n % nb == 0, f"n={n} must be a multiple of nb={nb}"
    p = n // nb
    return {
        (i, j): a[..., i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
        for i in range(p) for j in range(i + 1)
    }, p


def assemble_lower(tiles, p: int, nb: int, dtype):
    """Lower-triangle tiles -> full (..., n, n) lower-triangular matrix."""
    n = p * nb
    batch = tiles[(0, 0)].shape[:-2]
    out = jnp.zeros(batch + (n, n), dtype=dtype)
    for (i, j), t in tiles.items():
        out = out.at[..., i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].set(
            t.astype(dtype))
    tri = jnp.tril(jnp.ones((n, n), dtype=bool))
    return jnp.where(tri, out, jnp.zeros((), dtype=dtype))


def tile_cholesky(a, nb: int, policy: PrecisionPolicy, *, schedule=None):
    """Factor SPD `a` (..., n, n) -> lower-triangular L in policy.hi dtype.

    Faithful Algorithm 1.  For mode="full" every tile is hi (reference DP
    path).  For mode="dst" use dst_cholesky instead.  Leading axes of `a`
    are a batch of independent factorizations (one per candidate theta);
    every tile op below batches over them.

    `schedule` opts into the dynamic task runtime (DESIGN.md §12): pass a
    `repro.sched.SchedConfig` and the same task DAG executes out of order
    on a threaded worker pool, bitwise-identical to the sequential loop
    nest below.  Eager-only (the runtime is host-side Python) -- leave it
    None inside jit/vmap.
    """
    if policy.mode == "dst":
        raise ValueError("use dst_cholesky for the DST baseline")
    if schedule is not None:
        from ..sched.runtime import scheduled_tile_cholesky
        l, _report = scheduled_tile_cholesky(a, nb, policy, schedule)
        return l
    # telemetry at the dispatch boundary only: under jit/vmap `a` is a
    # tracer and maybe_span degrades to the no-op (DESIGN.md §13)
    with obs.maybe_span("core.tile_cholesky", a, n=a.shape[-1], nb=nb,
                        mode=policy.mode) as sp:
        l = _tile_cholesky_eager(a, nb, policy)
        if sp is not obs.NULL_SPAN:
            l.block_until_ready()   # time the math, not the async dispatch
        return l


def _tile_cholesky_eager(a, nb: int, policy: PrecisionPolicy):
    hi, lo = policy.hi, policy.lo
    tiles, p = split_tiles(a, nb)

    def tier(i, j):
        d = abs(i - j)
        if policy.mode == "three_tier" and d >= policy.diag_thick2:
            return policy.lo2
        return lo

    # initial storage conversion (lines 2-6, dlag2s on off-band tiles)
    store = {}
    with jax.named_scope("convert"):
        for (i, j), t in tiles.items():
            store[(i, j)] = t.astype(hi) if policy.in_band(i, j) \
                else t.astype(tier(i, j))

    for k in range(p):
        with jax.named_scope("potrf"):
            l_kk = _potrf(store[(k, k)], hi)      # line 8: dpotrf
            store[(k, k)] = l_kk
            l_kk_lo = l_kk.astype(lo)             # line 9: dlag2s -> tmp

        for i in range(k + 1, p):                 # panel TRSMs
            if policy.in_band(i, k):              # line 12: dtrsm
                with jax.named_scope("trsm_hi"):
                    store[(i, k)] = _trsm_right_lt(l_kk, store[(i, k)], hi,
                                                   hi)
            else:                                 # line 14: strsm (+15 sconv2d)
                t = tier(i, k)
                with jax.named_scope("trsm_lo"):
                    store[(i, k)] = _trsm_right_lt(
                        l_kk_lo, store[(i, k)].astype(lo), policy.solve_dtype,
                        t)

        for j in range(k + 1, p):                 # trailing update
            with jax.named_scope("update_hi"):
                # sconv2d'd copy if off-band
                a_jk_hi = store[(j, k)].astype(hi)
                a_jk_hi_t = jnp.swapaxes(a_jk_hi, -1, -2)
                # line 19: dsyrk, always hi
                store[(j, j)] = store[(j, j)] - a_jk_hi @ a_jk_hi_t
            for i in range(j + 1, p):
                if policy.in_band(i, j):          # line 25: dgemm
                    with jax.named_scope("update_hi"):
                        a_ik = store[(i, k)].astype(hi)
                        store[(i, j)] = store[(i, j)] - a_ik @ a_jk_hi_t
                else:                             # line 27: sgemm (lo storage)
                    t = tier(i, j)
                    with jax.named_scope("update_lo"):
                        upd = lo_matmul(store[(i, k)],
                                        jnp.swapaxes(store[(j, k)], -1, -2),
                                        policy, tier=lo)
                        store[(i, j)] = (store[(i, j)].astype(lo)
                                         - upd).astype(t)

    with jax.named_scope("convert"):
        return assemble_lower(store, p, nb, hi)


def dst_cholesky(a, nb: int, diag_thick: int, hi=jnp.float32):
    """DST / independent-blocks baseline (paper Sec. V-B, Fig. 1b).

    The matrix is replaced by its block-diagonal of "super-tiles" of
    diag_thick x diag_thick tiles (off-super-tile entries = zero), and each
    independent block is factored in full precision.  Returns the list of
    per-block factors plus the block slices (the block-diagonal factor).
    Leading axes of `a` batch over independent matrices.
    """
    n = a.shape[-1]
    assert n % nb == 0
    super_nb = diag_thick * nb
    blocks = []
    start = 0
    while start < n:
        stop = min(start + super_nb, n)
        blk = a[..., start:stop, start:stop].astype(hi)
        blocks.append((slice(start, stop), jnp.linalg.cholesky(blk)))
        start = stop
    return blocks


def dst_assemble(blocks, n: int, dtype=jnp.float32):
    """Assemble the block-diagonal factor into a dense (n, n) matrix."""
    out = jnp.zeros((n, n), dtype=dtype)
    for sl, l in blocks:
        out = out.at[sl, sl].set(l.astype(dtype))
    return out


def reference_cholesky(a, hi=jnp.float32):
    """Plain dense Cholesky in hi precision (DP(100%) reference)."""
    return jnp.linalg.cholesky(a.astype(hi))
