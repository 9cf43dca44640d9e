"""Paper Fig. 7: Monte-Carlo parameter-estimation accuracy boxplots.

Weak/medium/strong correlation x {DP, MP variants, DST variants}; N_REP
synthetic datasets per case (paper: 100 at n=40k; scaled to n=256/N_REP=6
for CPU -- the qualitative ordering DP ~ MP >> DST is the claim under
test; tests/test_mle_kriging.py asserts it)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PrecisionPolicy, fit_mle, make_loglik
from repro.covariance import CORRELATION_LEVELS, make_dataset

from .common import emit

N = 256
NB = 32
N_REP = 6


def fit_variant(ds, policy, max_iters=40):
    ll = make_loglik(ds.locs, ds.z, policy, nb=NB, nu_static=0.5)
    f = lambda th: ll(jnp.concatenate([th, jnp.array([0.5])]))
    res = fit_mle(f, [0.7, 0.15], max_iters=max_iters)
    return res.theta, res.n_evals


def variants(p):
    return {
        "DP": PrecisionPolicy.full(jnp.float32),
        "DP10-SP90": PrecisionPolicy.from_dp_percent(p, 0.10),
        "DP40-SP60": PrecisionPolicy.from_dp_percent(p, 0.40),
        "DP90-SP10": PrecisionPolicy.from_dp_percent(p, 0.90),
        "DST-DP70": PrecisionPolicy.dst(
            PrecisionPolicy.from_dp_percent(p, 0.70).diag_thick),
        "DST-DP90": PrecisionPolicy.dst(
            PrecisionPolicy.from_dp_percent(p, 0.90).diag_thick),
    }


def run(n_rep=N_REP):
    p = N // NB
    results = {}
    for level, theta0 in CORRELATION_LEVELS.items():
        for vname, pol in variants(p).items():
            ests = []
            evals = []
            for rep in range(n_rep):
                ds = make_dataset(jax.random.fold_in(jax.random.PRNGKey(42),
                                                     rep * 7 + 1),
                                  N, theta0, nu_static=0.5)
                th, ne = fit_variant(ds, pol)
                ests.append(th)
                evals.append(ne)
            est = np.stack(ests)
            key = f"fig7/{level}/{vname}"
            results[key] = est
            emit(key, 0.0,
                 f"var_hat={est[:,0].mean():.3f}+-{est[:,0].std():.3f} "
                 f"range_hat={est[:,1].mean():.4f}+-{est[:,1].std():.4f} "
                 f"true=({float(theta0[0])} {float(theta0[1])}) "
                 f"evals={np.mean(evals):.0f}")
    return results


if __name__ == "__main__":
    run()
