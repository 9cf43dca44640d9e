"""Pure-jnp oracle for the lo-tier trailing update kernel."""

import jax.numpy as jnp


def lo_trailing_update_ref(c, off, *, k: int, t: int, accum_dtype=jnp.float32):
    """off[i, j] -= c[i-k-1] c[j-k-1]^T for k+1 <= j and j+t <= i <= p-1,
    tile by tile, with the kernel's rounding: an accum-dtype product,
    rounded to off's dtype, subtracted in off's dtype."""
    for i in range(k + 1 + t, off.shape[0]):
        for j in range(k + 1, i - t + 1):
            acc = jnp.matmul(c[i - k - 1], c[j - k - 1].T,
                             preferred_element_type=accum_dtype)
            off = off.at[i, j].set(off[i, j] - acc.astype(off.dtype))
    return off
