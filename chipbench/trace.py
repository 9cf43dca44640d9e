"""Reduce a profiler trace of one run to device busy time and its parts.

Reads the `.xplane.pb` that `jax.profiler` writes, with JAX's own
`ProfileData`.  Device planes are `/device:TPU:<i>`; on each, the line
`XLA Ops` holds one event per device operation and `XLA Modules` one per
program execution, named after the jitted function (`jit_<name>(<id>)`).
Host spans are the benchmark's `TraceAnnotation`s, all named `cb.*`, on
the host plane.  Everything is clipped to the host span `cb.window`.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "cb.window"
SPAN_PREFIX = "cb."
NS = 1e-9


@dataclass
class Reduction:
    window_s: float                   # length of the host span cb.window
    busy_s: float                     # union of device-op intervals, mean over chips
    chips: int
    modules: dict = field(default_factory=dict)   # name -> [seconds, count] of
                                                  # executions centred in the window
    ops: dict = field(default_factory=dict)       # op name -> seconds
    gaps: list = field(default_factory=list)      # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_ms(self, name: str) -> float | None:
        """Mean device milliseconds per execution of `jit_<name>`, or None
        where the trace holds no such execution."""
        s, count = self.modules.get(f"jit_{name}", (0.0, 0))
        return s / count * 1e3 if count else None

    def executions(self, name: str) -> int:
        """Executions of module `jit_<name>` centred in the window."""
        return self.modules.get(f"jit_{name}", (0.0, 0))[1]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(directory: str | Path) -> Path:
    found = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(found[-1])


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """Group the unrolled copies of one operation: `fusion.123` -> `fusion`."""
    return re.sub(r"(\.\d+)+$", "", event_name.split(" ")[0].lstrip("%"))


def reduce_planes(planes) -> Reduction:
    """Reduce planes given as [(name, [(line name, [(event name, start_ns,
    duration_ns)])])] -- the shape `load` gives."""
    spans = []
    devices = []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            devices.append(dict(lines))
        elif pname.startswith("/host"):
            for _, events in lines:
                spans.extend((s, s + d, n) for n, s, d in events
                             if n.startswith(SPAN_PREFIX))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    if not devices:
        raise ValueError("trace has no device plane")
    w0, w1 = windows[0]

    busy_total = 0.0
    modules: dict = {}
    ops: dict = {}
    merged_first = None
    for lines in devices:
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        clipped = [(max(s, w0), min(s + d, w1), n) for n, s, d in op_events
                   if s < w1 and s + d > w0]
        merged = _merge([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in merged)
        if merged_first is None:
            merged_first = merged
        for s, e, n in clipped:
            k = _op_name(n)
            ops[k] = ops.get(k, 0.0) + (e - s) * NS
        for n, s, d in lines.get(MODULES_LINE, []):
            if not w0 <= s + d / 2 < w1:      # robust to a small clock offset
                continue
            k = _module_name(n)
            entry = modules.setdefault(k, [0.0, 0])
            entry[0] += d * NS
            entry[1] += 1

    gaps = []
    edges = [w0] + [x for s, e in merged_first for x in (s, e)] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 > g0:
            mid = 0.5 * (g0 + g1)
            inner = [(e - s, n) for s, e, n in spans if s <= mid < e]
            label = min(inner)[1] if inner else "no host span"
            gaps.append((label, (g1 - g0) * NS))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=(w1 - w0) * NS,
                     busy_s=busy_total * NS / len(devices),
                     chips=len(devices), modules=modules, ops=ops, gaps=gaps)


def load(path: str | Path):
    """Planes of an `.xplane.pb` as plain tuples (see `reduce_planes`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(pl.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                  for ev in ln.events])
                       for ln in pl.lines])
            for pl in pd.planes]


def reduce(path: str | Path) -> Reduction:
    return reduce_planes(load(path))
