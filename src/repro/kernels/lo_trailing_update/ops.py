"""Jitted public wrapper for the lo-tier trailing update kernel."""

from functools import partial

import jax
import jax.numpy as jnp

from .lo_trailing_update import lo_trailing_update_pallas


@partial(jax.jit, static_argnames=("k", "t", "accum_dtype", "interpret"))
def lo_trailing_update(c, off, *, k: int, t: int, accum_dtype=jnp.float32,
                       interpret: bool = True):
    return lo_trailing_update_pallas(c, off, k=k, t=t, accum_dtype=accum_dtype,
                                     interpret=interpret)
