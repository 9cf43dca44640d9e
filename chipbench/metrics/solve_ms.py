"""Device time of the log-determinant and forward solve per evaluation
(ms): ops under the named scope `geostat_loglik_step/solve` in
`jit_cb_eval`."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_eval", "geostat_loglik_step/solve")
