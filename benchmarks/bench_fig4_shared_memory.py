"""Paper Fig. 4: execution time per likelihood iteration, DP vs
mixed-precision variants, shared-memory (this CPU).

Faithful regime: the paper's literal pair (DP=fp64 band, SP=fp32 off-band)
under x64 -- on CPU fp32 GEMMs genuinely run ~2x fp64, so the paper's
speedup mechanism is measurable here (the TPU fp32/bf16 pair is evaluated
via the roofline model in bench_fig6/bench_lm_roofline)."""

import jax
import jax.numpy as jnp

from repro.core import PrecisionPolicy, make_loglik
from repro.covariance import make_dataset

from .common import emit, time_call


def run(ns=(256, 512, 1024), nb=64):
    rows = []
    with jax.enable_x64(True):
        for n in ns:
            ds = make_dataset(jax.random.PRNGKey(0), n, [1.0, 0.1, 0.5],
                              nu_static=0.5)
            theta = jnp.asarray(ds.theta0, jnp.float64)
            t_dp = time_call(jax.jit(make_loglik(
                ds.locs, ds.z, PrecisionPolicy.full(jnp.float64), nb=nb,
                nu_static=0.5, use_tiles=True)), theta)
            p = n // nb
            for dp_pct in (0.1, 0.4, 0.9):
                pol = PrecisionPolicy.from_dp_percent(p, dp_pct,
                                                      pair="paper_cpu")
                t_mp = time_call(jax.jit(make_loglik(
                    ds.locs, ds.z, pol, nb=nb, nu_static=0.5)), theta)
                label = f"fig4/n{n}/DP{int(dp_pct*100)}%-SP{100-int(dp_pct*100)}%"
                emit(label, t_mp, f"speedup_vs_DP={t_dp/t_mp:.2f}x")
                rows.append((n, dp_pct, t_dp, t_mp))
            emit(f"fig4/n{n}/DP100%", t_dp, "baseline")
    return rows


if __name__ == "__main__":
    run()
