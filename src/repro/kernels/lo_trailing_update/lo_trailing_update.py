"""Pallas TPU kernel: the panel sweep's lo-tier trailing update, in place.

At panel step k the factored column holds c[m] = tile (k+1+m, k), in lo.
The tiles of the off-band storage `off` (p, p, nb, nb) that the step must
update are the lower trapezoid

    (i, j)  with  k+1 <= j  and  j+t <= i <= p-1,

each by off[i, j] -= c[i-k-1] c[j-k-1]^T (the paper's sgemm: lo operands,
an accum-dtype MXU accumulator, the product rounded to lo, the difference
stored in lo).  Everything else in `off` -- the band's share, the upper
triangle, the columns already factored -- is neither read nor written:
`off` is aliased to the output, so untouched tiles are never copied.

The grid is one program per trapezoid tile, row-major, with the tiles'
(i, j) scalar-prefetched.  Neighbours in a row share i, so the row panel
tile c[i] is not fetched again between them.  One step at nb = 1024 holds
two panel tiles, the target and the result, each double-buffered, plus an
accum-dtype product: about 20 MiB of VMEM, above the default scoped limit,
so the limit is raised to what the blocks need.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def trapezoid(p: int, k: int, t: int) -> tuple[np.ndarray, np.ndarray]:
    """Global (i, j) of the tiles that step k updates, row-major."""
    pairs = [(i, j) for i in range(k + 1 + t, p) for j in range(k + 1, i - t + 1)]
    ii, jj = zip(*pairs) if pairs else ((), ())
    return np.asarray(ii, np.int32), np.asarray(jj, np.int32)


def _kernel(ii_ref, jj_ref, ci_ref, cj_ref, off_ref, out_ref, *, accum_dtype):
    del ii_ref, jj_ref                      # used by the index maps only
    acc = jax.lax.dot_general(ci_ref[...], cj_ref[...],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=accum_dtype)
    out_ref[...] = off_ref[...] - acc.astype(out_ref.dtype)


def _vmem_bytes(nb: int, lo_dtype, accum_dtype) -> int:
    """Three input blocks and one output block, double-buffered, and the
    product; with room for Mosaic's own scratch."""
    tile = nb * nb
    need = 8 * tile * jnp.dtype(lo_dtype).itemsize \
        + tile * jnp.dtype(accum_dtype).itemsize
    return int(need * 1.5) + (4 << 20)


def lo_trailing_update_pallas(c, off, *, k: int, t: int,
                              accum_dtype=jnp.float32, interpret: bool = True):
    """off with the step-k lo update applied to its lower trapezoid.

    c: (p-k-1, nb, nb) in off's dtype; off: (p, p, nb, nb).  Returns off
    itself where the trapezoid is empty.
    """
    p, nb = off.shape[0], off.shape[-1]
    assert c.shape == (p - k - 1, nb, nb) and c.dtype == off.dtype, \
        (c.shape, c.dtype, off.shape, off.dtype)
    ii, jj = trapezoid(p, k, t)
    if ii.size == 0:
        return off
    sq = pl.squeezed
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ii.size,),
        in_specs=[
            pl.BlockSpec((sq, nb, nb), lambda g, ii, jj: (ii[g] - k - 1, 0, 0)),
            pl.BlockSpec((sq, nb, nb), lambda g, ii, jj: (jj[g] - k - 1, 0, 0)),
            pl.BlockSpec((sq, sq, nb, nb), lambda g, ii, jj: (ii[g], jj[g], 0, 0)),
        ],
        out_specs=pl.BlockSpec((sq, sq, nb, nb),
                               lambda g, ii, jj: (ii[g], jj[g], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype),
        out_shape=jax.ShapeDtypeStruct(off.shape, off.dtype),
        grid_spec=grid_spec,
        input_output_aliases={4: 0},        # off (after ii, jj, c, c)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(nb, off.dtype, accum_dtype)),
        interpret=interpret,
        name="lo_trailing_update",
    )(jnp.asarray(ii), jnp.asarray(jj), c, c, off)
