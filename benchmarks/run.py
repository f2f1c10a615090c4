# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json out.json`` additionally dumps the same rows as JSON and
# ``--only a,b`` restricts the run to named sections.
from __future__ import annotations

import argparse
import json
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")


def _sections():
    from . import (
        bench_ac,
        bench_batched,
        bench_factorization,
        bench_level_stats,
        bench_levelization,
        bench_modes,
        bench_robustness,
        bench_sparse_rhs,
        bench_sweep_sharded,
        bench_threshold,
        bench_transient,
    )

    return [
        ("levelization",
         "=== Table II: levelization (relaxed vs double-U detection) ===",
         bench_levelization.main),
        ("preprocessing",
         "=== Planner: preprocessing vs numeric breakdown per engine ===",
         bench_levelization.preprocessing_breakdown),
        ("factorization", "=== Table I: numeric factorization ===",
         bench_factorization.main),
        ("modes", "=== Table III: kernel-mode ablation ===", bench_modes.main),
        ("threshold", "=== Fig 12: panel threshold sweep ===",
         bench_threshold.main),
        ("level_stats", "=== Fig 10: level parallelism profile ===",
         bench_level_stats.main),
        ("transient", "=== End-to-end transient (SPICE loop) ===",
         bench_transient.main),
        ("batched",
         "=== Batched refactorization throughput (one plan, B matrices) ===",
         bench_batched.main),
        ("robustness", "=== Robustness layer: scaling / guard / refinement ===",
         bench_robustness.main),
        ("ac", "=== AC sweep: batched complex vs per-frequency loop ===",
         bench_ac.main),
        ("sparse_rhs",
         "=== Sparse-RHS trisolve: reach-pruned vs full schedule ===",
         bench_sparse_rhs.main),
        ("sweep_sharded",
         "=== Sharded sweep scaling over sub-meshes of jax.devices() ===",
         bench_sweep_sharded.main),
    ]


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="benchmarks.run")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write the result rows as JSON")
    parser.add_argument("--only", metavar="NAMES", default=None,
                        help="comma-separated section names to run "
                             "(default: all)")
    args = parser.parse_args(argv)

    sections = _sections()
    if args.only:
        wanted = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = wanted - {name for name, _, _ in sections}
        if unknown:
            parser.error(f"unknown sections {sorted(unknown)}; available: "
                         f"{[name for name, _, _ in sections]}")
        sections = [s for s in sections if s[0] in wanted]

    from repro.compile_cache import enable_compile_cache

    from .common import RESULTS

    enable_compile_cache()
    RESULTS.clear()     # a second in-process main() must not accumulate rows
    print("name,us_per_call,derived")
    for _, header, fn in sections:
        print(f"# {header}")
        fn()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(RESULTS, f, indent=2)
        print(f"# wrote {len(RESULTS)} rows to {args.json}")


if __name__ == "__main__":
    main()
