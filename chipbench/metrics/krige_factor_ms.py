"""Device time of the tile factorization per kriging request (ms): ops
under the named scope `krige/factor` in `jit_cb_krige`."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_krige", "krige/factor")
