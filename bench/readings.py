#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process.

    python bench/readings.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--value-dtype float32]

Each seed runs the cell's window as ``bench/run.py`` does (same traffic,
same entry points, same reference check) but the plan is built once and
shared, so a dozen seeds cost one set-up.  With the configuration's value
dtype this gives the lower readings the limits are set from; with
``--value-dtype float32`` the control's upper readings.  One line per seed,
then the worst reading of each number over the seeds.  Exits 2 without a
TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--value-dtype", default=None)
    args = ap.parse_args(argv)
    worst: dict = {}
    try:
        for seed in args.seeds:
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             value_dtype=args.value_dtype,
                             plan_cache="default", t0=time.perf_counter())
            print(json.dumps({"seed": seed, "correct": r["correct"],
                              "attempted": r["attempted"],
                              "failed": r["failed"],
                              "checks": r["checks"]}), flush=True)
            for k, c in r["checks"].items():
                v = c["value"]
                prev = worst.get(k, 0.0)
                worst[k] = None if v is None or prev is None else max(prev, v)
    except run.NoChip as e:
        print(f"bench: {e}; nothing runs", file=sys.stderr)
        return 2
    print(json.dumps({"workload": args.workload,
                      "value_dtype": args.value_dtype, "seeds": args.seeds,
                      "worst": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
