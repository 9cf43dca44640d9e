"""Evaluations per completed fit (`MLEResult.n_evals`), over the window."""


def read(rctx):
    return rctx.counters.get("evals_per_fit")
