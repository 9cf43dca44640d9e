#!/usr/bin/env python3
"""Chip benchmark of the geostatistics system: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Draws the cell's data and traffic from the seed on the host, compiles
and warms the program's entry (set-up), drives it for `--seconds` of
closed-loop traffic (the window), then compares a seeded sample of what
the window produced with the fp64 host reference.  The last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer metrics read from a profiler trace of the window), `device`,
with `--trace 1` a `breakdown`, and last `checks`, each compared number
beside its limit (also the last lines of standard error).

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.  `--rehearse` runs every cell (or the one named)
at the configuration's tiny rehearsal sizes on any backend and prints
no metric and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from chipbench import drivers, peaks, spec, trace  # noqa: E402

CACHE_DIR = spec.ROOT / ".jax_cache"
TRACE_DIR = spec.ROOT / ".chipbench_trace"
REHEARSE_SECONDS = 2.0
# the traced part of a --trace 1 run's window: some hundreds of device
# programs, a trace that reads in seconds
TRACE_SECONDS = 5.0


class Counters:
    """Compiles and compile-cache reads, from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def use_cache():
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however small or quick to compile."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: spec.Cell, seed: int, seconds: float, *, traced: bool,
             rehearse: bool, counters: Counters, log=print) -> dict:
    """Set-up, window and comparison of one run; returns the result."""
    ctx = drivers.Ctx.make(cell.config, cell.traffic, seed, rehearse=rehearse,
                           trace_s=min(TRACE_SECONDS, seconds) if traced else None)
    drv = drivers.driver(ctx, cell.chips)
    drv.setup()
    setup_s = time.perf_counter() - T_START
    compiles_before = counters.compiles
    split = ", ".join(f"{k}={v:.6f}" for k, v in ctx.split.items())
    log(f"setup: setup_s={setup_s:.6f} ({split}, other="
        f"{setup_s - sum(ctx.split.values()):.6f}) cache_hits={counters.hits} "
        f"cache_misses={counters.misses} compiles={counters.compiles}",
        flush=True)

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the benchmark's spans, not every call
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    drv.window(seconds)                # stops the profiler after TRACE_SECONDS
    in_window = counters.compiles - compiles_before
    dev = jax.devices()[0]
    # the runtime's counter leaves out a program's temporaries on some
    # runtimes: the compiler's count of what the program holds stands beside it
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:cell.chips])
    held = drivers.compiled_bytes(drv.exe)
    peak_bytes = max(in_use, held)
    drv.free()
    attempted, failed = drv.outcome()
    log(f"window: seconds={seconds} attempted={attempted} failed={failed} "
        f"compiles_in_window={in_window} counters={ctx.counters}", flush=True)
    log(f"memory: peak_bytes_in_use={in_use} program_bytes={held}", flush=True)

    t0 = time.perf_counter()
    checks = drv.checks()
    log(f"reference: seconds={time.perf_counter() - t0:.6f}", flush=True)
    correct = all(v <= lim for v, lim in checks.values())

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if traced:
        red = trace.reduce(trace.find_xplane(TRACE_DIR))
        rctx = SimpleNamespace(trace=red, counters=ctx.counters, ctx=ctx,
                               peak=peaks.peak(dev.device_kind))
        values = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"])(rctx)
            if v is not None:
                values[m["name"]] = v
    else:
        values = dict(drv.end_to_end(), setup_s=setup_s)
        values = {m["name"]: values[m["name"]] for m in cell.end_to_end
                  if m["name"] in values}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": int(peak_bytes)}
    if traced:
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def rehearse(names, counters) -> int:
    """Every named cell at its rehearsal sizes, on whatever backend JAX has."""
    for name in names:
        cell = spec.cell(name)
        res = run_cell(cell, seed=12345, seconds=REHEARSE_SECONDS,
                       traced=False, rehearse=True, counters=counters,
                       log=lambda *a, **k: None)
        checks = {k: v["value"] for k, v in res["checks"].items()}
        print(f"rehearsed {name}: attempted={res['attempted']} failed="
              f"{res['failed']} checks={checks} (tiny sizes on "
              f"{res['device']['platform']}; not a result)", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="every cell (or --workload) at tiny sizes; no result")
    args = ap.parse_args(argv)
    counters = Counters()

    if args.rehearse:
        names = [args.workload] if args.workload else \
            [w["name"] for w in spec.load_benchmark()["workloads"]]
        return rehearse(names, counters)
    if not args.workload:
        ap.error("--workload is required")
    cell = spec.cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"need {cell.chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    peaks.peak(devs[0].device_kind)          # an unknown chip is an error
    use_cache()
    res = run_cell(cell, args.seed, args.seconds, traced=bool(args.trace),
                   rehearse=False, counters=counters)
    for k, v in res["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
