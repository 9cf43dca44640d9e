"""Device time of the panel factorization per evaluation (ms): ops under
the named scope `geostat_loglik_step/factor` in `jit_cb_eval`."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_eval", "geostat_loglik_step/factor")
