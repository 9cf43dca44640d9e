"""Whole evaluation step's share of the chip's bf16 peak (%).

The algorithm's FLOPs of one log-likelihood evaluation (covariance
entries, n^3/3, the n^2 solve; `flops.loglik_flops`) times the
evaluations (`jit_cb_eval` executions) per second of the traced window.
"""

from chipbench import flops


def read(rctx):
    evals = rctx.trace.executions("cb_eval")
    if not evals:
        return None
    rate = evals / rctx.trace.window_s
    return 100.0 * flops.loglik_flops(rctx.ctx.n) * rate \
        / rctx.peak["bf16_flops_per_s"]
