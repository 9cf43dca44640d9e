#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program and its control.

    python chipbench/limits.py --workload <cell> --seeds 101-112 \
        [--control-seeds 101-104] [--seconds 2]

For each seed, drives the cell's traffic through the program for a short
window and compares a sample of its answers with the fp64 reference,
exactly as a run does; then the same for the control: the program with
its far tier in the configuration's `control` dtype (fp8 in place of
bfloat16), the band as configured.  One process, so drawn fields and
reference answers are made once and compiled programs are read from the
cache after their first compile.  Prints one line per seed and variant, and last
the largest program reading and the smallest control reading of each
compared number.  Runs on the chip; not one of the benchmark's runs.
"""

from __future__ import annotations

import argparse
import functools
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import data, drivers, reference, run, spec  # noqa: E402


def _seeds(text: str) -> list[int]:
    if not text:
        return []
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def _memo(fn):
    """Cache a pure host function on the bytes of its array arguments."""
    cache = {}

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        key = tuple(getattr(a, "tobytes", lambda a=a: repr(a))() for a in args) \
            + tuple(sorted(kw.items()))
        if key not in cache:
            cache[key] = fn(*args, **kw)
        return cache[key]

    return wrapped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse:
        run.use_cache()
    data.field = _memo(data.field)
    reference.loglik = _memo(reference.loglik)
    reference.krige_mean = _memo(reference.krige_mean)
    cell = spec.cell(args.workload)
    worst: dict = {}
    for control, seeds in ((False, _seeds(args.seeds)),
                           (True, _seeds(args.control_seeds))):
        for seed in seeds:
            ctx = drivers.Ctx.make(cell.config, cell.traffic, seed,
                                   rehearse=args.rehearse, control=control)
            drv = drivers.driver(ctx, cell.chips)
            drv.setup()
            drv.window(args.seconds)
            drv.free()
            checks = drv.checks()
            label = "control" if control else "program"
            print(f"{label} seed={seed} attempted,failed={drv.outcome()} "
                  + " ".join(f"{k}={v!r}" for k, (v, _) in checks.items()),
                  flush=True)
            for k, (v, _) in checks.items():
                best = worst.setdefault((label, k), v)
                worst[(label, k)] = min(best, v) if control else max(best, v)
    for (label, k), v in sorted(worst.items()):
        kind = "smallest" if label == "control" else "largest"
        print(f"{kind} {label} {k}={v!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
