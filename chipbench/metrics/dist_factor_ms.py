"""Device time of the sharded `fori` factorization per chip and
evaluation (ms): ops under `geostat_loglik_distributed/factor` in
`jit_cb_eval4`, the loop's collectives included."""

from chipbench import per_chip


def read(rctx):
    return per_chip.scope_ms(rctx, "cb_eval4",
                             "geostat_loglik_distributed/factor")
