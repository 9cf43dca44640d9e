"""The traffic generator: what every kind of closed-loop driver shares.

A traffic file `traffic/<traffic>.json` names its `kind` and gives the
parameters.  The driver of that kind is the module `kinds/<kind>.py`,
found by name: its class `Driver` draws the inputs from the seed,
compiles and warms the program's entry (`exe`), runs the measured
window, and afterwards compares a sample of what the window produced
with the plain reference; its tuple `CHIPS` names the chip counts it
places its work on.  A new kind of traffic is a new module there.
"""

from __future__ import annotations

import importlib
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np
import jax
from jax.profiler import TraceAnnotation

from . import data, program


class WindowClosed(Exception):
    """Raised inside an evaluation once the measured window has closed."""


@dataclass
class Ctx:
    config: dict
    traffic: dict
    seed: int
    n: int
    nb: int
    diag_thick: int
    trace_s: float | None = None  # profile the window's first seconds
    control: bool = False        # the program with the control's far tier
    split: dict = field(default_factory=dict)   # set-up seconds by part
    counters: dict = field(default_factory=dict)  # read by per-layer metrics

    @classmethod
    def make(cls, config, traffic, seed, *, rehearse=False, **kw):
        sizes = config["rehearse"] if rehearse else config
        return cls(config, traffic, seed, sizes["n"], sizes["nb"],
                   sizes["diag_thick"], **kw)

    @property
    def policy(self):
        return program.policy(self.config, self.diag_thick,
                              control=self.control)

    @property
    def model(self):
        c = self.config
        return dict(nu=c["nu"], jitter=c["jitter"])

    @contextmanager
    def timed(self, part: str):
        """Add the block's seconds to set-up part `part`."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.split[part] = self.split.get(part, 0.0) \
                + time.perf_counter() - t0


def kind(name: str):
    """The module `kinds/<name>.py`."""
    return importlib.import_module(f"{__package__}.kinds.{name}")


def driver(ctx: Ctx, chips: int = 1):
    """The driver of the traffic's kind; refuses a chip count that the
    kind does not place its work on."""
    mod = kind(ctx.traffic["kind"])
    if chips not in mod.CHIPS:
        raise ValueError(f"traffic kind {ctx.traffic['kind']!r} runs on "
                         f"{mod.CHIPS} chip(s), not {chips}")
    return mod.Driver(ctx)


def compile_exe(ctx: Ctx, fn, *args):
    with ctx.timed("compile"):
        return jax.jit(fn).lower(*args).compile()


def compiled_bytes(exe) -> int:
    """Device bytes the compiled program holds while it runs: arguments,
    outputs, temporaries and code, by the compiler's own analysis."""
    m = exe.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes + m.generated_code_size_in_bytes
               - m.alias_size_in_bytes)


def sample(ctx: Ctx, count: int) -> np.ndarray:
    """Indices of the answers compared, drawn from the seed."""
    k = min(int(ctx.traffic["check_sample"]), count)
    return np.sort(data.rng(ctx.seed, 2).choice(count, size=k, replace=False))


def put(*arrays):
    return [jax.device_put(a) for a in arrays]


class Window:
    """The measured window: its host span `cb.window`, and with a trace
    the profiler, which stops after the window's first `trace_s` seconds
    so that the trace stays small enough to read inside a run."""

    def __init__(self, seconds: float, trace_s: float | None):
        self.seconds, self.trace_s = seconds, trace_s

    def __enter__(self):
        self.span = TraceAnnotation("cb.window")
        self.span.__enter__()
        self.t0 = time.perf_counter()
        self.tracing = self.trace_s is not None
        return self

    def closed(self, now: float) -> bool:
        """Whether the window is over at `now`; stops the profiler once
        its part of the window has passed."""
        if now - self.t0 >= (self.trace_s if self.tracing else self.seconds):
            self._close_span()
        return now - self.t0 >= self.seconds

    def _close_span(self):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False

    def __exit__(self, *exc):
        self._close_span()


def closed_loop(call, inputs, seconds: float, label: str, trace_s):
    """Call `call(*inputs[i])` back to back until `seconds` have passed;
    returns [(start, end)] and the outputs, each waited for."""
    times, outs = [], []
    with Window(seconds, trace_s) as window:
        i = 0
        while True:
            with TraceAnnotation(label):
                t0 = time.perf_counter()
                out = call(*inputs[i % len(inputs)])
                jax.block_until_ready(out)
                t1 = time.perf_counter()
            times.append((t0, t1))
            outs.append(out)
            i += 1
            if window.closed(t1):
                break
    return times, outs
