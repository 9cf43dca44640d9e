#!/usr/bin/env python3
"""Record the scoped test trace on a TPU: `python chipbench/tests/record_scoped_trace.py`.

What `record_trace.py` records, from the program as it now stands (with
its named scopes), into tests/data/evals_scoped_tiny.xplane.pb; the
op -> scope map comes from the HLO the trace carries (`chipbench/scopes.py`).
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))

from chipbench import scopes  # noqa: E402
from chipbench.tests import record_trace  # noqa: E402


def main() -> int:
    record_trace.OUT = HERE / "data" / "evals_scoped_tiny.xplane.pb"
    rc = record_trace.main()
    got = scopes.reduce(record_trace.OUT)
    print(f"device time under {scopes.NO_SCOPE!r}: {100 * got.share():.4f}%")
    return rc


if __name__ == "__main__":
    sys.exit(main())
