"""Device time of the sharded log-determinant and forward solve per chip
and evaluation (ms): ops under `geostat_loglik_distributed/solve` in
`jit_cb_eval4`."""

from chipbench import per_chip


def read(rctx):
    return per_chip.scope_ms(rctx, "cb_eval4",
                             "geostat_loglik_distributed/solve")
