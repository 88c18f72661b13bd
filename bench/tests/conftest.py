"""Tests of the benchmark's own code, on the CPU.

Run from the checkout's root:
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests`` (the repository's
own test run collects ``tests/`` only)."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
