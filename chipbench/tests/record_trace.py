#!/usr/bin/env python3
"""Record the small chip trace that tests/test_trace.py reads.

    python chipbench/tests/record_trace.py        (on a TPU host)

Runs the `synth32k.evals` traffic at its configuration's rehearsal
size with the profiler on for a fraction of a second, and copies the
`.xplane.pb` to tests/data/.  Prints what the trace holds: planes,
lines and a few event names.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))

from chipbench import run, spec, trace  # noqa: E402

OUT = HERE / "data" / "evals_tiny.xplane.pb"


def main() -> int:
    counters = run.Counters()
    res = run.run_cell(spec.cell("synth32k.evals"), seed=2027, seconds=0.05,
                       traced=True, rehearse=True, counters=counters)
    src = trace.find_xplane(run.TRACE_DIR)
    OUT.parent.mkdir(exist_ok=True)
    shutil.copyfile(src, OUT)
    for pname, lines in trace.load(OUT):
        print("plane", pname)
        for lname, events in lines:
            names = sorted({e[0] for e in events})
            print(f"  line {lname!r}: {len(events)} events, e.g. {names[:6]}")
    print("result", res)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
