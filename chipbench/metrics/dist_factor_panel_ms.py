"""Device time of the `fori` sweep's panel per chip and evaluation (ms):
ops under the loop body's `potrf`, `trsm_hi` and `trsm_lo` scopes in
`geostat_loglik_distributed/factor`, in `jit_cb_eval4`, the band
all-gathers they wait on included."""

from chipbench import per_chip


def read(rctx):
    return per_chip.scope_ms(rctx, "cb_eval4",
                             "geostat_loglik_distributed/factor",
                             "potrf", "trsm_hi", "trsm_lo")
