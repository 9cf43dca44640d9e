"""Device time of the panel steps' POTRF and TRSMs per evaluation (ms):
ops under `factor/potrf`, `factor/trsm_hi` and `factor/trsm_lo` in
`jit_cb_eval`."""

from chipbench import scopes

PHASES = ("potrf", "trsm_hi", "trsm_lo")


def read(rctx):
    return scopes.scope_ms(
        rctx, "cb_eval", *(f"geostat_loglik_step/factor/{p}" for p in PHASES))
