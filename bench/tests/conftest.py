"""Tests of the benchmark's own code, on the CPU at toy sizes."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
