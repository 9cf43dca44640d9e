"""Share of the traced window in which no operation ran on the device (%)."""


def read(rctx):
    return 100.0 * rctx.trace.idle_share
