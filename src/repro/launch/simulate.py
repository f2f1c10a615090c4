"""Circuit-simulation driver — the paper's end-to-end application.

  PYTHONPATH=src python -m repro.launch.simulate --nx 8 --ny 8 \
      --t-end 0.05 --dt 0.005
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..circuit import rc_grid_circuit, transient
from ..compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=8)
    ap.add_argument("--ny", type=int, default=8)
    ap.add_argument("--t-end", type=float, default=0.05)
    ap.add_argument("--dt", type=float, default=0.005)
    ap.add_argument("--no-diodes", action="store_true")
    ap.add_argument("--ordering", default="auto")
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # the circuit is simulated in float64, which JAX runs only in 64-bit mode
    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()

    ckt = rc_grid_circuit(args.nx, args.ny, with_diodes=not args.no_diodes,
                          seed=args.seed)
    res = transient(ckt, args.t_end, args.dt, ordering=args.ordering,
                    use_pallas=args.pallas)
    print(f"nodes: {args.nx * args.ny}  steps: {len(res.times)}  "
          f"newton: {res.newton_iters.sum()}  factorizations: {res.n_factorizations}")
    print(f"setup {res.setup_seconds:.2f}s  solve {res.solve_seconds:.2f}s  "
          f"max residual {res.max_residual:.2e}")
    assert np.isfinite(res.voltages).all()
    return res


if __name__ == "__main__":
    main()
