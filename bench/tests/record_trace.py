#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs on a TPU: a 64-node RC ladder, the ``newton`` mix, two traced Newton
steps through the same traced window as ``bench/run.py --trace 1``, and
copies the profiler's ``.xplane.pb`` to ``<out.xplane.pb>``.  It prints the
trace's planes and their lines, and the reduction of the window.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import gen  # noqa: E402
import run  # noqa: E402
import trace as tm  # noqa: E402
from traffic import Traffic  # noqa: E402

STEPS = 2


def main(out: str) -> int:
    import jax

    devs = run.devices_for(1, require_tpu=True)
    n, (indptr, indices, data), net = gen.make_matrix(
        {"generator": "rc_ladder", "args": {"n": 64, "seed": 0}})
    mix = run.load_mix("newton")
    traffic = Traffic(mix, net, 7)
    glu = run.build_glu(n, (indptr, indices), data, "float64", None)
    for i in range(int(mix["warmup"])):
        traffic.step(glu, i, traced=True)
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        run.traced_window(traffic, glu, STEPS, 60.0, tdir)
        path = tm.find_xplane(tdir)
        shutil.copyfile(path, out)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        print(plane.name, [ln.name for ln in plane.lines])
    spans, devices = tm.read(out, [devs[0].id])
    r = tm.reduce(spans, devices)
    print(json.dumps({k: r[k] for k in ("window_s", "busy_s", "span_count",
                                        "span_device_s", "device_ops")}))
    print("bytes", os.path.getsize(out), "jax", jax.__version__)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
