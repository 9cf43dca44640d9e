"""Device time of the `fori` sweep's lo trailing update per chip and
evaluation (ms): ops under the `update_lo` scope of the loop body in
`geostat_loglik_distributed/factor`, in `jit_cb_eval4`: the full-width
masked lo GEMM of every step (about three times the trailing
trapezoid's FLOPs), its mask and write-back."""

from chipbench import per_chip


def read(rctx):
    return per_chip.scope_ms(rctx, "cb_eval4",
                             "geostat_loglik_distributed/factor", "update_lo")
