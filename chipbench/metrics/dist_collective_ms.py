"""Device time of collectives per chip and evaluation (ms): all-gather,
all-reduce, reduce-scatter, collective-permute and all-to-all ops on the
trace's synchronous `XLA Ops` line, whole or as the `-start` / `-done`
halves of an asynchronous one, and the TPU compiler's async-collective
fusions (`per_chip.COLLECTIVE`), over the `jit_cb_eval4` executions.
The in-flight spans of asynchronous ones, on the `Async XLA Ops` line
beside compute, do not count."""

from chipbench import per_chip


def read(rctx):
    return per_chip.collective_ms(rctx, "cb_eval4")
