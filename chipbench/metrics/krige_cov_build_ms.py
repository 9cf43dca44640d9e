"""Device time of building Sigma_oo and Sigma_no per kriging request
(ms): ops under the named scope `krige/cov_build` in `jit_cb_krige`."""

from chipbench import scopes


def read(rctx):
    return scopes.scope_ms(rctx, "cb_krige", "krige/cov_build")
