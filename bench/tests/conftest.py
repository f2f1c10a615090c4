"""The benchmark's own tests: ``python -m pytest bench/tests``.

They run on the CPU at sizes a test run holds, with 64-bit JAX, and import
the benchmark's modules the way ``bench/run.py`` does."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_ENABLE_X64", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))
