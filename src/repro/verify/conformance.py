"""Kernel + Cholesky-variant conformance sweep against the fp64 oracles.

Every record is a flat dict (JSON-serializable) with an `id`, the registry
key components, and the measured metrics, so the same sweep output feeds

  * the bound check (`check_records` -> tests/test_conformance_sweep.py),
  * the golden regression gate (golden.py), and
  * the accuracy columns in benchmarks (benchmarks/bench_accuracy.py).

Coverage (acceptance floor: >= 3 problem sizes x 3 conditioning regimes):

  sweep_cholesky   tile_cholesky under every registered policy mode, the
                   banded panel_cholesky performance path, and the
                   dst_cholesky tapering baseline, on the canonical
                   SIZES x REGIMES grid of Matern problems.
  sweep_kernels    all four Pallas kernel pairs (matern_cov, mp_gemm's
                   mp_syrk, blocked_potrf, mp_attention) ops.py vs ref.py,
                   each across >= 3 shapes x 3 conditioning knobs.
  sweep_kriging    held-out kriging PMSE vs the fp64 exact predictor for
                   the full and mixed policies on every grid problem.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.likelihood import dst_loglik, loglik_from_factor
from ..core.panel_cholesky import (
    assemble_from_banded,
    banded_loglik,
    build_banded_covariance,
    panel_cholesky_banded,
)
from ..core.precision import PrecisionPolicy
from ..core.tile_cholesky import dst_assemble, dst_cholesky, tile_cholesky
from ..core.kriging import krige_pmse
from ..covariance.matern import matern_covariance
from .bounds import dtype_pair, lookup_bound
from .generators import (
    CONDITIONS,
    CholeskyProblem,
    attention_problem,
    cholesky_problems,
    spd_matrix,
)
from .oracles import (
    backward_error,
    exact_factor,
    exact_kriging_pmse,
    exact_loglik,
    loglik_drift,
    pmse_drift,
    rel_frobenius,
)

# The policy set under test: one entry per paper variant (plus the bf16 and
# three-tier beyond-paper policies).  diag_thick=2 on the p in {2, 4, 6}
# grid covers the degenerate band >= p case at n=64 and genuinely banded
# factorizations at n >= 128.
#
# three_tier uses diag_thick2=3, not 2: fp8(e4m3) tiles one sub-diagonal
# off the band quantize O(1) correlation mass coarsely enough to make the
# strongly-correlated n=192 problem indefinite (NaN factor).  The sweep
# pins the widest-known-good setting; the NaN cliff is a measured property
# of the fp8 far field, recorded here so nobody "fixes" it by loosening a
# bound.
def default_policies() -> dict[str, PrecisionPolicy]:
    return {
        "full_f32": PrecisionPolicy.full(jnp.float32),
        "mixed_f32f32_t2": PrecisionPolicy(mode="mixed", hi=jnp.float32,
                                           lo=jnp.float32, diag_thick=2),
        "mixed_f32bf16_t1": PrecisionPolicy.tpu(diag_thick=1),
        "mixed_f32bf16_t2": PrecisionPolicy.tpu(diag_thick=2),
        "three_tier_t1_t3": PrecisionPolicy.three_tier(diag_thick=1,
                                                       diag_thick2=3),
    }


_DST_THICK = 2


def _chol_record(rid: str, prob: CholeskyProblem, policy_mode: str,
                 pair: str, diag_thick, l, ll) -> dict:
    l_ref = exact_factor(prob.cov)
    ll_ref = exact_loglik(prob.cov, prob.z)
    return {
        "id": rid,
        "kind": "cholesky",
        "mode": policy_mode,
        "pair": pair,
        "diag_thick": diag_thick,
        "regime": prob.regime,
        "n": prob.n,
        "factor_rel": rel_frobenius(l, l_ref),
        "backward_rel": backward_error(l, prob.cov),
        "loglik_drift": loglik_drift(ll, ll_ref),
    }


def sweep_cholesky(problems=None, policies=None, *,
                   paper_pair: bool = True) -> list[dict]:
    """tile / panel / dst variants x the policy set x the problem grid."""
    import jax

    problems = cholesky_problems() if problems is None else problems
    policies = default_policies() if policies is None else policies
    records = []
    for prob in problems:
        # --- faithful tile engine, every policy ---------------------------
        for label, pol in policies.items():
            rid = f"chol/tile/{label}/{prob.name}"
            with obs.span("verify.cell", id=rid, kind="cholesky"):
                l = tile_cholesky(prob.cov.astype(pol.hi), prob.nb, pol)
                ll = float(loglik_from_factor(l, prob.z))
            records.append(_chol_record(
                rid, prob, pol.mode, dtype_pair(pol), pol.diag_thick,
                np.asarray(l, np.float64), ll))

        # --- the paper's literal CPU pair (fp64 band / fp32 off-band) ----
        if paper_pair:
            rid = f"chol/tile/paper_f64f32_t2/{prob.name}"
            with obs.span("verify.cell", id=rid, kind="cholesky"):
                with jax.enable_x64(True):
                    pol = PrecisionPolicy.paper_cpu(diag_thick=2)
                    cov64 = jnp.asarray(np.asarray(prob.cov, np.float64))
                    l = tile_cholesky(cov64, prob.nb, pol)
                    ll = float(loglik_from_factor(l, prob.z))
            records.append(_chol_record(
                rid, prob, pol.mode, dtype_pair(pol), pol.diag_thick,
                np.asarray(l, np.float64), ll))

        # --- banded panel performance path (production mixed pair) -------
        rid = f"chol/panel/mixed_f32bf16_t2/{prob.name}"
        with obs.span("verify.cell", id=rid, kind="cholesky"):
            pol = policies.get("mixed_f32bf16_t2") or PrecisionPolicy.tpu(2)
            band, off = build_banded_covariance(
                prob.locs, prob.theta, nb=prob.nb, policy=pol, nu_static=0.5,
                jitter=1e-6)
            t = min(pol.diag_thick, prob.p)
            band, off = panel_cholesky_banded(band, off, pol)
            l_panel = assemble_from_banded(band, off, t)
            ll_panel = float(banded_loglik(band, off, prob.z, t))
        records.append(_chol_record(
            rid, prob, pol.mode, dtype_pair(pol), pol.diag_thick,
            np.asarray(l_panel, np.float64), ll_panel))

        # --- DST tapering baseline ---------------------------------------
        rid = f"chol/dst/t{_DST_THICK}/{prob.name}"
        with obs.span("verify.cell", id=rid, kind="cholesky"):
            blocks = dst_cholesky(prob.cov, prob.nb, diag_thick=_DST_THICK)
            l_dst = dst_assemble(blocks, prob.n)
            ll_dst = float(dst_loglik(blocks, prob.z))
        dst_pol = PrecisionPolicy.dst(_DST_THICK)
        records.append(_chol_record(
            rid, prob, "dst", dtype_pair(dst_pol), _DST_THICK,
            np.asarray(l_dst, np.float64), ll_dst))
    return records


def sweep_kriging(problems=None, policies=None) -> list[dict]:
    """Held-out kriging PMSE drift vs the fp64 exact predictor."""
    from ..core.likelihood import build_covariance

    problems = cholesky_problems() if problems is None else problems
    if policies is None:
        pols = default_policies()
        policies = {k: pols[k] for k in ("full_f32", "mixed_f32bf16_t2")}
    records = []
    for prob in problems:
        n_new = prob.nb                       # hold out one tile row
        n_obs = prob.n - n_new
        locs_o, locs_n = prob.locs[:n_obs], prob.locs[n_obs:]
        z_o, y = prob.z[:n_obs], prob.z[n_obs:]
        cov_oo = build_covariance(locs_o, prob.theta, nu_static=0.5,
                                  jitter=1e-6, dtype=jnp.float32)
        sigma_no = matern_covariance(locs_n, locs_o, prob.theta,
                                     nu_static=0.5)
        ref = exact_kriging_pmse(cov_oo, z_o, sigma_no, y)
        for label, pol in policies.items():
            with obs.span("verify.cell", id=f"krige/{label}/{prob.name}",
                          kind="kriging"):
                score = float(krige_pmse(locs_o, z_o, locs_n, y, prob.theta,
                                         pol, nb=prob.nb, nu_static=0.5,
                                         jitter=1e-6))
            records.append({
                "id": f"krige/{label}/{prob.name}",
                "kind": "kriging",
                "mode": pol.mode,
                "pair": dtype_pair(pol),
                "diag_thick": pol.diag_thick,
                "regime": prob.regime,
                "n": prob.n,
                "pmse_rel": pmse_drift(score, ref),
            })
    return records


# ---------------------------------------------------------------------------
# kernel pairs (ops.py vs ref.py)
# ---------------------------------------------------------------------------


def _scale_rel(out, ref) -> float:
    """max |out - ref| normalized by the reference magnitude scale."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _kernel_record(rid, kernel, out, ref, **extra) -> dict:
    rec = {
        "id": rid,
        "kind": "kernel",
        "kernel": kernel,
        "max_rel": _scale_rel(out, ref),
        "max_abs": float(np.max(np.abs(np.asarray(out, np.float64)
                                       - np.asarray(ref, np.float64)))),
    }
    rec.update(extra)
    return rec


def sweep_kernels() -> list[dict]:
    """All four Pallas kernel pairs, each on >= 3 shapes x 3 regimes."""
    import jax

    from ..covariance import random_locations
    from ..kernels.blocked_potrf.ops import potrf
    from ..kernels.blocked_potrf.ref import potrf_ref
    from ..kernels.matern_cov.ops import matern_cov
    from ..kernels.matern_cov.ref import matern_cov_ref
    from ..kernels.mp_attention.ops import banded_decode_attention, quantize_kv
    from ..kernels.mp_attention.ref import banded_decode_attention_ref
    from ..kernels.mp_gemm.ops import mp_syrk
    from ..kernels.mp_gemm.ref import mp_syrk_ref

    records = []

    # matern_cov: 3 tile shapes x 3 smoothness regimes
    for m, n, bm, bn in ((64, 64, 32, 32), (128, 64, 64, 64),
                         (128, 128, 64, 64)):
        la = random_locations(jax.random.PRNGKey(11), m)
        lb = random_locations(jax.random.PRNGKey(12), n)
        for nu in (0.5, 1.5, 2.5):
            rid = f"kern/matern_cov/m{m}n{n}_nu{nu}"
            with obs.span("verify.cell", id=rid, kind="kernel"):
                theta = jnp.array([1.3, 0.12, nu])
                out = matern_cov(la, lb, theta, nu=nu, bm=bm, bn=bn)
                ref = matern_cov_ref(la, lb, theta, nu=nu)
            records.append(_kernel_record(rid, "matern_cov", out, ref))

    # mp_syrk: 3 shapes x 3 band widths (band width = precision regime)
    for m, k, bm, bk in ((128, 64, 64, 64), (256, 128, 64, 64),
                         (256, 64, 128, 64)):
        p = jax.random.normal(jax.random.PRNGKey(13), (m, k), jnp.float32)
        for band in (1, 2, 4):
            rid = f"kern/mp_syrk/m{m}k{k}_band{band}"
            with obs.span("verify.cell", id=rid, kind="kernel"):
                out = mp_syrk(p, band_blocks=band, bm=bm, bk=bk)
                ref = mp_syrk_ref(p, band_blocks=band, bm=bm, bk=bk)
            records.append(_kernel_record(rid, "mp_syrk", out, ref))

    # blocked_potrf: 3 sizes x 3 condition numbers
    for n in (32, 64, 128):
        for cname, cond in CONDITIONS.items():
            rid = f"kern/blocked_potrf/n{n}_{cname}"
            with obs.span("verify.cell", id=rid, kind="kernel"):
                a = spd_matrix(17 + n, n, cond=cond)
                out = potrf(a)
                ref = potrf_ref(a)
            records.append(_kernel_record(
                rid, "blocked_potrf", out, ref,
                backward_rel=backward_error(out, a)))

    # mp_attention: 3 cache shapes x 3 logit scales (softmax sharpness)
    for i, (b, g, d, sn, sf, blk) in enumerate(
            ((2, 4, 64, 128, 256, 128), (1, 8, 128, 256, 128, 64),
             (4, 1, 64, 128, 128, 128))):
        for scale in (0.5, 1.0, 2.0):
            rid = f"kern/mp_attention/shape{i}_scale{scale}"
            with obs.span("verify.cell", id=rid, kind="kernel"):
                q, kn, vn, kf, vf = attention_problem(
                    21 + i, b, g, d, sn, sf, scale=scale)
                kq, vq, scales = quantize_kv(kf, vf, blk=blk)
                near_len = jnp.full((b,), sn, jnp.int32)
                far_len = jnp.full((b,), sf, jnp.int32)
                sm = 1.0 / float(np.sqrt(d))
                out = banded_decode_attention(q, kn, vn, near_len, kq, vq,
                                              scales, far_len, blk=blk,
                                              sm_scale=sm)
                ref = banded_decode_attention_ref(q, kn, vn, near_len, kq, vq,
                                                  scales, far_len, blk=blk,
                                                  sm_scale=sm)
            rec = _kernel_record(rid, "mp_attention", out, ref)
            rec.pop("max_rel")  # softmax outputs are O(1); abs is the metric
            records.append(rec)
    return records


def run_conformance(*, problems=None, policies=None,
                    kernels: bool = True) -> list[dict]:
    """The full sweep: cholesky variants + kriging + kernel pairs.

    The seeded problems are drawn with the non-partitionable threefry
    stream, the one golden/accuracy.json and the bounds were measured on
    (JAX >= 0.5 defaults to the partitionable stream, which draws other
    problems from the same seeds).
    """
    import jax

    with jax.threefry_partitionable(False):
        records = sweep_cholesky(problems, policies)
        records += sweep_kriging(problems)
        if kernels:
            records += sweep_kernels()
    return records


def check_records(records) -> list[tuple[str, str]]:
    """(record id, violation message) for every metric out of bounds."""
    violations = []
    for rec in records:
        if rec["kind"] == "kernel":
            bound = lookup_bound("kernel", rec["kernel"])
        else:
            bound = lookup_bound(rec["mode"], rec["pair"],
                                 rec.get("diag_thick"), rec.get("regime"))
        for msg in bound.violations(rec):
            violations.append((rec["id"], msg))
    return violations
