"""The system under test: the only module of the benchmark that imports it.

The program lives in `src/` beside this directory.  A checkout that
holds the benchmark alone fails here, before any result is printed.
Tests replace the entries below to plant faults.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core import PrecisionPolicy  # noqa: E402
from repro.core.kriging import krige  # noqa: E402
from repro.core.mle import fit_mle  # noqa: E402
from repro.core.panel_cholesky import (banded_loglik,  # noqa: E402
                                       build_banded_covariance,
                                       geostat_loglik_step,
                                       panel_cholesky_banded)


def policy(cfg: dict, diag_thick: int, *, control: bool = False):
    """The configuration's precision policy at band width `diag_thick`;
    with `control`, its far tier in the configuration's control dtype."""
    pair = dict(cfg["precision"], **(cfg["control"] if control else {}))
    return PrecisionPolicy(mode="mixed", hi=jnp.dtype(pair["hi"]),
                           lo=jnp.dtype(pair["lo"]), diag_thick=diag_thick,
                           solve_dtype=jnp.float32, accum_dtype=jnp.float32)


__all__ = ["PrecisionPolicy", "banded_loglik", "build_banded_covariance",
           "fit_mle", "geostat_loglik_step", "krige", "panel_cholesky_banded",
           "policy"]
