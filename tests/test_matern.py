"""Matern covariance + Bessel K_nu correctness (vs scipy) and properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special as sp

from conftest import HAVE_HYPOTHESIS, HYPOTHESIS_SKIP_REASON

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings
    import strategies as sts

from repro.covariance import kv, matern, matern_covariance, pairwise_distance


NUS = [0.1, 0.3, 0.5, 0.9, 1.0, 1.27, 1.5, 2.0, 2.5, 3.3, 4.9, 7.2]
XS = np.array([1e-4, 1e-2, 0.1, 0.5, 1.0, 1.9, 2.0, 2.1, 3.0, 5.0, 10.0, 30.0, 80.0])


@pytest.mark.parametrize("nu", NUS)
def test_kv_matches_scipy_f64(nu):
    with jax.enable_x64(True):
        ours = np.asarray(kv(jnp.float64(nu), jnp.asarray(XS, jnp.float64)))
    ref = sp.kv(nu, XS)
    np.testing.assert_allclose(ours, ref, rtol=1e-10)


def test_kv_f32_reasonable():
    ours = np.asarray(kv(jnp.float32(1.27), jnp.asarray(XS, jnp.float32)))
    ref = sp.kv(1.27, XS)
    np.testing.assert_allclose(ours, ref, rtol=2e-4)


@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5])
def test_matern_closed_form_matches_general(nu):
    theta = jnp.array([1.3, 0.2, nu])
    r = jnp.linspace(0.0, 2.0, 64)
    a = matern(r, theta, nu_static=nu)
    b = matern(r, theta)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_matern_at_zero_is_variance():
    theta = jnp.array([2.7, 0.1, 1.1])
    assert float(matern(jnp.array(0.0), theta)) == pytest.approx(2.7, rel=1e-6)


def test_matern_monotone_decreasing():
    theta = jnp.array([1.0, 0.2, 0.8])
    r = jnp.linspace(0.0, 3.0, 100)
    c = np.asarray(matern(r, theta))
    assert np.all(np.diff(c) <= 1e-7)


def test_matern_gradients_finite():
    f = lambda th: matern(jnp.array(0.3), th)[()]
    g = jax.grad(f)(jnp.array([1.0, 0.1, 1.27]))
    assert np.all(np.isfinite(np.asarray(g)))


if HAVE_HYPOTHESIS:
    @given(sts.matern_nus, sts.bessel_args)
    @settings(max_examples=30, deadline=None)
    def test_kv_positive_and_decreasing_in_x(nu, x):
        v1 = float(kv(nu, jnp.float32(x)))
        v2 = float(kv(nu, jnp.float32(x * 1.1)))
        assert v1 > 0 and v2 > 0 and v2 <= v1 * (1 + 1e-5)
else:
    @pytest.mark.skip(reason=HYPOTHESIS_SKIP_REASON)
    def test_kv_positive_and_decreasing_in_x():
        pass


def test_pairwise_euclidean():
    a = jnp.array([[0.0, 0.0], [1.0, 0.0]])
    d = pairwise_distance(a, a)
    np.testing.assert_allclose(np.asarray(d), [[0, 1], [1, 0]], atol=1e-6)


def test_pairwise_haversine_symmetry_and_scale():
    a = jnp.array([[40.0, 20.0], [41.0, 20.0], [40.0, 21.0]])
    d = np.asarray(pairwise_distance(a, a, metric="haversine"))
    assert d[0, 0] == pytest.approx(0.0, abs=1e-5)
    np.testing.assert_allclose(d, d.T, atol=1e-5)
    # 1 degree of longitude at lat 20 ~ cos(20 deg) degrees of arc
    assert d[0, 1] == pytest.approx(np.cos(np.deg2rad(20.0)), rel=1e-3)
    assert d[0, 2] == pytest.approx(1.0, rel=1e-3)  # 1 degree of latitude


def test_covariance_is_spd(small_dataset):
    cov = matern_covariance(small_dataset.locs, small_dataset.locs,
                            jnp.array([1.0, 0.1, 0.5]), nu_static=0.5)
    evals = np.linalg.eigvalsh(np.asarray(cov, np.float64))
    assert evals.min() > -1e-5
