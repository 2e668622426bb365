"""The benchmark's own tests run on the CPU: the trace reduction on
synthetic traces, and each driver at tiny shapes with the program's XLA
analogues standing in for the Pallas kernels."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
