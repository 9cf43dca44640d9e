import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU, by path: pytest chipbench/tests
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
