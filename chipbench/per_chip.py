"""Trace readings per chip and per execution, for cells on several chips.

`trace.Reduction` counts one execution of a program on each device
plane, so on four chips `executions` is four times the calls, and
`ops` sums each operation's seconds over the chips; `scopes` already
averages its seconds over the chips.  These helpers divide both sides
by the chips, so a reading on four chips is what one chip spent on one
evaluation.
"""

from __future__ import annotations

import re

from chipbench import scopes

# the collectives XLA inserts for a sharded program: whole, as the
# `-start` / `-done` halves of an asynchronous one, or as the TPU
# compiler's `async-collective-start` / `-done` fusions around one
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|async-collective)"
                        r"(-start|-done|-fusion)?$")


def calls(rctx, name: str) -> float:
    """Executions of `jit_<name>` in the traced window, per chip."""
    return rctx.trace.executions(name) / rctx.trace.chips


def scope_ms(rctx, name: str, root: str, *phases: str):
    """Device milliseconds per chip and execution of `jit_<name>` under
    the scope path `root`, or with `phases` only under the scopes of those
    names nested anywhere below `root` (a loop's body adds `while/body/...`
    between them); None where the program ran no such op."""
    count = calls(rctx, name)
    if not count:
        return None
    seconds = scopes.reduce(scopes.traced_file()).seconds.get(name, {})
    found = [s for path, s in seconds.items()
             if (path == root or path.startswith(root + "/"))
             and (not phases
                  or set(phases) & set(path[len(root):].split("/")))]
    return sum(found) / count * 1e3 if found else None


def collective_ms(rctx, name: str):
    """Device milliseconds per chip and execution of `jit_<name>` spent in
    collective operations on the trace's synchronous `XLA Ops` line: whole
    ones and both halves of an asynchronous one, the time a chip's core is
    held by them.  What an asynchronous one does in flight, beside compute,
    is on the `Async XLA Ops` line, which `trace.reduce_planes` does not
    read.  None where the program did not run in the window."""
    count = calls(rctx, name)
    if not count:
        return None
    s = sum(v for op, v in rctx.trace.ops.items() if COLLECTIVE.match(op))
    return s / rctx.trace.chips / count * 1e3
