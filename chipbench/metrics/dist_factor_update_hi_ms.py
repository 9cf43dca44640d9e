"""Device time of the `fori` sweep's band (hi tier) updates per chip and
evaluation (ms): ops under the loop body's `update_hi` scope in
`geostat_loglik_distributed/factor`, in `jit_cb_eval4`: the roll-aligned
einsums over the band's sub-diagonals."""

from chipbench import per_chip


def read(rctx):
    return per_chip.scope_ms(rctx, "cb_eval4",
                             "geostat_loglik_distributed/factor", "update_hi")
