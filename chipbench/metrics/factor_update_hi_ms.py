"""Device time of the band (hi tier) updates per evaluation (ms): ops
under `factor/update_hi` in `jit_cb_eval`, the dsyrk/dgemm einsums."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_eval",
                           "geostat_loglik_step/factor/update_hi")
