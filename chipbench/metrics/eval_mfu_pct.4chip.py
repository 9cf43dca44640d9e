"""Whole sharded evaluation's share of the four chips' bf16 peak (%).

The algorithm's FLOPs of one log-likelihood evaluation
(`flops.loglik_flops`: covariance entries, n^3/3, the n^2 solve) times
the evaluations per second of the traced window (`jit_cb_eval4`
executions per chip), over the chips' summed peak.  The `fori` sweep's
full-width masked updates issue about three times those FLOPs; they do
not count.
"""

from chipbench import flops, per_chip


def read(rctx):
    evals = per_chip.calls(rctx, "cb_eval4")
    if not evals:
        return None
    rate = evals / rctx.trace.window_s
    return 100.0 * flops.loglik_flops(rctx.ctx.n) * rate \
        / (rctx.trace.chips * rctx.peak["bf16_flops_per_s"])
