"""SPMD-reformulated geostat engine vs the banded numerical reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PrecisionPolicy, banded_loglik,
                        build_banded_covariance, panel_cholesky_banded)
from repro.core.distributed import (build_covariance_distributed,
                                    geostat_loglik_distributed,
                                    loglik_distributed,
                                    panel_cholesky_distributed)
from repro.covariance import make_dataset

N, NB, T = 256, 32, 2


@pytest.fixture(scope="module")
def ds():
    return make_dataset(jax.random.PRNGKey(1), N, [1.0, 0.1, 0.5],
                        nu_static=0.5)


@pytest.fixture(scope="module")
def ll_ref(ds):
    pol = PrecisionPolicy.tpu(diag_thick=T)
    band, off = build_banded_covariance(ds.locs, ds.theta0, nb=NB,
                                        policy=pol, nu_static=0.5)
    band, off = panel_cholesky_banded(band, off, pol)
    return float(banded_loglik(band, off, ds.z, T))


@pytest.mark.parametrize("version", ["masked_full", "aligned"])
def test_distributed_matches_banded(ds, ll_ref, version):
    pol = PrecisionPolicy.tpu(diag_thick=T)
    ll = float(geostat_loglik_distributed(ds.locs, ds.z, ds.theta0, nb=NB,
                                          policy=pol, nu_static=0.5,
                                          version=version))
    assert ll == pytest.approx(ll_ref, abs=1.0)


def test_distributed_band_region_is_zero_in_off(ds):
    pol = PrecisionPolicy.tpu(diag_thick=T)
    off, band = build_covariance_distributed(ds.locs, ds.theta0, nb=NB,
                                             policy=pol, nu_static=0.5)
    p = N // NB
    o = np.asarray(off, np.float32)
    for i in range(p):
        for j in range(p):
            blk = o[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB]
            if i - j >= T:
                assert np.abs(blk).max() > 0
            else:
                assert np.abs(blk).max() == 0


def test_distributed_full_policy_matches_dense(ds):
    """full-precision distributed factorization == LAPACK cholesky."""
    from repro.core import build_covariance, reference_cholesky, loglik_from_factor
    pol = PrecisionPolicy.full(jnp.float32)
    ll = float(geostat_loglik_distributed(ds.locs, ds.z, ds.theta0, nb=NB,
                                          policy=pol, nu_static=0.5))
    cov = build_covariance(ds.locs, ds.theta0, nu_static=0.5, jitter=1e-6,
                           dtype=jnp.float32)
    l_ref = reference_cholesky(cov, jnp.float32)
    ll_dense = float(loglik_from_factor(l_ref, ds.z))
    assert ll == pytest.approx(ll_dense, abs=0.5)


def test_distributed_takes_the_jitter(ds):
    """`jitter` reaches the diagonal; its default is the build's 1e-6."""
    pol = PrecisionPolicy.tpu(diag_thick=T)
    f = jax.jit(lambda th, jitter: geostat_loglik_distributed(
        ds.locs, ds.z, th, nb=NB, policy=pol, nu_static=0.5, jitter=jitter,
        version="fori"))
    default = float(geostat_loglik_distributed(
        ds.locs, ds.z, ds.theta0, nb=NB, policy=pol, nu_static=0.5,
        version="fori"))
    assert float(f(ds.theta0, 1e-6)) == pytest.approx(default, rel=1e-6)
    assert float(f(ds.theta0, 1e-2)) != pytest.approx(default, rel=1e-4)


def test_distributed_jits(ds):
    pol = PrecisionPolicy.tpu(diag_thick=T)
    f = jax.jit(lambda th: geostat_loglik_distributed(
        ds.locs, ds.z, th, nb=NB, policy=pol, nu_static=0.5))
    v1 = float(f(ds.theta0))
    v2 = float(f(ds.theta0 * 1.1))
    assert np.isfinite(v1) and np.isfinite(v2) and v1 != v2


def test_distributed_build_matches_banded_for_near_neighbours():
    """Points 1e-4 apart, far from the origin: the |a|^2+|b|^2-2ab^T
    distance form cancels there (and a TPU's one-pass bf16 matmul made the
    covariance indefinite); the build must match the one-chip build."""
    pol = PrecisionPolicy.full()
    key = jax.random.PRNGKey(3)
    centres = 0.8 + 0.1 * jax.random.uniform(key, (N // 4, 1, 2))
    offsets = 1e-4 * jnp.array([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0],
                                [1.0, 1.0]])
    locs = (centres + offsets[None]).reshape(N, 2)
    theta = jnp.array([1.0, 0.03])
    off, band = build_covariance_distributed(locs, theta, nb=NB, policy=pol,
                                             nu_static=0.5)
    band_ref, _ = build_banded_covariance(locs, theta, nb=NB, policy=pol,
                                          nu_static=0.5)
    np.testing.assert_allclose(np.asarray(band), np.asarray(band_ref),
                               rtol=2e-5, atol=2e-6)
