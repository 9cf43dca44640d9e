"""Device time of the two triangular solves and the product per kriging
request (ms): ops under the named scope `krige/solve` in `jit_cb_krige`."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_krige", "krige/solve")
