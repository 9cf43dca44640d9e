"""Device time of the off-band (lo tier) update per evaluation (ms): ops
under `factor/update_lo` in `jit_cb_eval`: the lo GEMM, the mask and the
write-back."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_eval",
                           "geostat_loglik_step/factor/update_lo")
