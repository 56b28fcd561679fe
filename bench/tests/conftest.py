"""Tests of the benchmark, run by path: ``python -m pytest bench/tests``.

They run on the CPU at small sizes; nothing here needs a chip."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
