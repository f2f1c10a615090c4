#!/usr/bin/env python3
"""Run the four-chip cell on four virtual CPU devices, at a size the CPU
holds, and print the result as the last line of standard output.

    python bench/tests/four_devices.py <case>

``case`` is ``sound``, ``float32`` (the control), ``traced`` (the traced
branch, with device planes made as ``cpu_trace`` makes them) or the name
of a fault in ``faults.FAULTS``, planted under the timed path.  The
devices exist only if the flag is set before JAX starts, so this runs as a
process of its own (``test_four_chips.py``).
"""
import json
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")
TESTS = Path(__file__).resolve().parent
sys.path[:0] = [str(TESTS), str(TESTS.parent), str(TESTS.parents[1] / "src")]

import pytest  # noqa: E402

import cpu_trace  # noqa: E402
import run  # noqa: E402
from faults import FAULTS  # noqa: E402
from test_checks import small_config  # noqa: E402

CELL = "rcladder17758.sweep100.4chip"


def main(case: str) -> int:
    mp = pytest.MonkeyPatch()
    if case in FAULTS:
        FAULTS[case](mp)
    elif case == "traced":
        cpu_trace.install(mp)
    elif case not in ("sound", "float32"):
        raise SystemExit(f"unknown case {case!r}")
    r = run.run_cell(CELL, 2**31 + 29, 5.0 if case == "traced" else 0.5,
                     case == "traced", config=small_config(CELL),
                     value_dtype="float32" if case == "float32" else None,
                     require_tpu=False, t0=time.perf_counter())
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
