"""Device time per kriging request: mean of the window's `cb_krige` (ms)."""


def read(rctx):
    return rctx.trace.module_ms("cb_krige")
