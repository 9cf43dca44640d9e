"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.covariance import random_locations
from repro.kernels.matern_cov.ops import matern_cov
from repro.kernels.matern_cov.ref import matern_cov_ref
from repro.kernels.mp_gemm.ops import mp_syrk
from repro.kernels.mp_gemm.ref import mp_syrk_ref
from repro.kernels.blocked_potrf.ops import potrf
from repro.kernels.blocked_potrf.ref import potrf_ref
from repro.kernels.mp_attention.ops import banded_decode_attention, quantize_kv
from repro.kernels.mp_attention.ref import banded_decode_attention_ref
from repro.kernels.lo_trailing_update.ops import lo_trailing_update
from repro.kernels.lo_trailing_update.ref import lo_trailing_update_ref
from repro.core import PrecisionPolicy
from repro.core.panel_cholesky import _lo_update, _lo_update_loop
from conftest import spd_matrix


# ----------------------------- matern_cov -----------------------------

@pytest.mark.parametrize("nu", [0.5, 1.5, 2.5])
@pytest.mark.parametrize("m,n,bm,bn", [(128, 128, 64, 64), (256, 128, 128, 128),
                                       (64, 192, 32, 64)])
def test_matern_cov_kernel_matches_ref(nu, m, n, bm, bn):
    key = jax.random.PRNGKey(0)
    la = random_locations(key, m)
    lb = random_locations(jax.random.PRNGKey(1), n)
    theta = jnp.array([1.3, 0.12, nu])
    out = matern_cov(la, lb, theta, nu=nu, bm=bm, bn=bn)
    ref = matern_cov_ref(la, lb, theta, nu=nu)
    # kernel uses the MXU-friendly |x|^2+|y|^2-2xy distance: fp32
    # cancellation for near-coincident points costs ~1e-4 relative
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matern_cov_kernel_dtypes(dtype):
    key = jax.random.PRNGKey(2)
    la = random_locations(key, 128)
    theta = jnp.array([1.0, 0.1, 0.5])
    out = matern_cov(la, la, theta, nu=0.5, bm=64, bn=64, out_dtype=dtype)
    assert out.dtype == dtype
    ref = matern_cov_ref(la, la, theta, nu=0.5, out_dtype=dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2, atol=1e-2)


def test_matern_cov_general_nu_fallback():
    la = random_locations(jax.random.PRNGKey(3), 128)
    theta = jnp.array([1.0, 0.1, 1.27])
    out = matern_cov(la, la, theta, nu=1.27)
    ref = matern_cov_ref(la, la, theta, nu=1.27)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ------------------------------ mp_gemm -------------------------------

@pytest.mark.parametrize("m,k,bm,bk,band", [
    (256, 128, 64, 64, 1), (256, 128, 64, 64, 2), (128, 256, 64, 128, 1),
    (256, 64, 128, 64, 4),  # band >= nblocks: all-hi
])
def test_mp_syrk_matches_ref(m, k, bm, bk, band):
    p = jax.random.normal(jax.random.PRNGKey(4), (m, k), jnp.float32)
    out = mp_syrk(p, band_blocks=band, bm=bm, bk=bk)
    ref = mp_syrk_ref(p, band_blocks=band, bm=bm, bk=bk)
    # sub-bf16-ulp accumulation-order noise between interpret-mode dot and
    # the jnp reference is expected on off-band blocks
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_mp_syrk_band_is_exact_offband_is_bf16():
    m, k, bm = 256, 128, 64
    p = jax.random.normal(jax.random.PRNGKey(5), (m, k), jnp.float32)
    out = np.asarray(mp_syrk(p, band_blocks=1, bm=bm, bk=k))
    exact = np.asarray(p) @ np.asarray(p).T
    # diagonal blocks exact to fp32
    for i in range(m // bm):
        sl = slice(i * bm, (i + 1) * bm)
        np.testing.assert_allclose(out[sl, sl], exact[sl, sl], rtol=1e-5)
    # off-diagonal blocks carry bf16 rounding (error ~1e-2 relative)
    off_err = np.abs(out[:bm, bm:2 * bm] - exact[:bm, bm:2 * bm]).max()
    assert 1e-5 < off_err / np.abs(exact).max() < 0.05


# ---------------------------- blocked_potrf ---------------------------

@pytest.mark.parametrize("n", [32, 64, 128, 256])
def test_potrf_matches_lapack(n):
    a = spd_matrix(jax.random.PRNGKey(6), n, cond=100.0)
    out = potrf(a)
    ref = potrf_ref(a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_potrf_batched():
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    a = jnp.stack([spd_matrix(k, 64) for k in keys])
    out = potrf(a)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(potrf_ref(a[i])),
                                   rtol=5e-4, atol=5e-4)


# -------------------------- lo_trailing_update ------------------------

LO_P, LO_NB = 6, 128


def _lo_step(t, k):
    """Step k's panel c and a full off-band storage, both bf16."""
    rng = np.random.default_rng(10 * t + k)
    off = jnp.asarray(rng.standard_normal((LO_P, LO_P, LO_NB, LO_NB)),
                      jnp.bfloat16)
    c = jnp.asarray(rng.standard_normal((LO_P - k - 1, LO_NB, LO_NB)) / 8,
                    jnp.bfloat16)
    return c, off


def _bits(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("t", [1, 2, 3])
@pytest.mark.parametrize("k", range(LO_P - 1))
def test_lo_trailing_update_matches_ref_and_loop(t, k):
    """Bitwise equal to the oracle and to the panel sweep's einsum loop
    (same operands, f32 accumulation, one bf16 rounding of the product,
    one of the difference), at every step, the last ones (m_t <= t, an
    empty trapezoid) included."""
    c, off = _lo_step(t, k)
    out = lo_trailing_update(c, off, k=k, t=t)
    ref = lo_trailing_update_ref(c, off, k=k, t=t)
    loop = _lo_update_loop(c, off, k=k, t=t, policy=PrecisionPolicy.tpu(t))
    assert out.dtype == off.dtype and out.shape == off.shape
    np.testing.assert_array_equal(_bits(out), _bits(ref))
    np.testing.assert_array_equal(_bits(out), _bits(loop))


@pytest.mark.parametrize("t", [1, 2, 3])
@pytest.mark.parametrize("k", range(LO_P - 1))
def test_lo_trailing_update_leaves_other_tiles_alone(t, k):
    """The band, the upper triangle and the columns <= k keep their bits;
    every trapezoid tile changes."""
    c, off = _lo_step(t, k)
    out = _bits(lo_trailing_update(c, off, k=k, t=t))
    before = _bits(off)
    for i in range(LO_P):
        for j in range(LO_P):
            touched = j >= k + 1 and i - j >= t
            assert np.array_equal(out[i, j], before[i, j]) != touched, (i, j)


def test_lo_update_takes_the_einsum_loop_on_cpu():
    """Off the TPU the step's lo update is the loop: no kernel in the
    CPU program, the same bits, and derivatives through it."""
    t, k = 1, 0
    c, off = _lo_step(t, k)
    pol = PrecisionPolicy.tpu(t)
    fn = lambda c, off: _lo_update(c, off, k=k, t=t, policy=pol)
    assert "tpu_custom_call" not in jax.jit(fn).lower(c, off).as_text()
    np.testing.assert_array_equal(
        _bits(fn(c, off)), _bits(_lo_update_loop(c, off, k=k, t=t, policy=pol)))
    g = jax.grad(lambda c: jnp.sum(fn(c, off).astype(jnp.float32)))(c)
    assert g.shape == c.shape and bool(jnp.all(jnp.isfinite(g)))


# ---------------------------- mp_attention ----------------------------

def _mk_attn(key, b, g, d, sn, sf, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, g, d), dtype)
    kn = jax.random.normal(ks[1], (b, sn, d), dtype)
    vn = jax.random.normal(ks[2], (b, sn, d), dtype)
    kf = jax.random.normal(ks[3], (b, sf, d), dtype)
    vf = jax.random.normal(ks[4], (b, sf, d), dtype)
    return q, kn, vn, kf, vf


@pytest.mark.parametrize("b,g,d,sn,sf,blk", [
    (2, 4, 64, 128, 256, 128), (1, 8, 128, 256, 128, 64), (4, 1, 64, 128, 128, 128),
])
def test_banded_attention_matches_ref(b, g, d, sn, sf, blk):
    q, kn, vn, kf, vf = _mk_attn(jax.random.PRNGKey(8), b, g, d, sn, sf)
    kq, vq, scales = quantize_kv(kf, vf, blk=blk)
    near_len = jnp.full((b,), sn, jnp.int32)
    far_len = jnp.full((b,), sf, jnp.int32)
    sm = 1.0 / np.sqrt(d)
    out = banded_decode_attention(q, kn, vn, near_len, kq, vq, scales, far_len,
                                  blk=blk, sm_scale=sm)
    ref = banded_decode_attention_ref(q, kn, vn, near_len, kq, vq, scales,
                                      far_len, blk=blk, sm_scale=sm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_banded_attention_ragged_lengths():
    b, g, d, sn, sf, blk = 2, 4, 64, 128, 256, 128
    q, kn, vn, kf, vf = _mk_attn(jax.random.PRNGKey(9), b, g, d, sn, sf)
    kq, vq, scales = quantize_kv(kf, vf, blk=blk)
    near_len = jnp.array([128, 70], jnp.int32)
    far_len = jnp.array([200, 0], jnp.int32)
    sm = 1.0 / np.sqrt(d)
    out = banded_decode_attention(q, kn, vn, near_len, kq, vq, scales, far_len,
                                  blk=blk, sm_scale=sm)
    ref = banded_decode_attention_ref(q, kn, vn, near_len, kq, vq, scales,
                                      far_len, blk=blk, sm_scale=sm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_quantization_error_is_small_but_nonzero():
    """int8 far cache: ~1% attention output error -- the accuracy/bytes
    trade the paper makes, at KV-cache scale."""
    b, g, d, sn, sf = 2, 4, 64, 128, 256
    q, kn, vn, kf, vf = _mk_attn(jax.random.PRNGKey(10), b, g, d, sn, sf)
    kq, vq, scales = quantize_kv(kf, vf)
    near_len = jnp.full((b,), sn, jnp.int32)
    far_len = jnp.full((b,), sf, jnp.int32)
    sm = 1.0 / np.sqrt(d)
    out = banded_decode_attention(q, kn, vn, near_len, kq, vq, scales, far_len,
                                  sm_scale=sm)
    # exact attention with the unquantized far segment
    k_all = jnp.concatenate([kn, kf], axis=1)
    v_all = jnp.concatenate([vn, vf], axis=1)
    scores = jnp.einsum("bgd,bsd->bgs", q, k_all) * sm
    p = jax.nn.softmax(scores, axis=-1)
    exact = jnp.einsum("bgs,bsd->bgd", p, v_all)
    err = float(jnp.max(jnp.abs(out - exact)))
    assert 1e-6 < err < 0.05
