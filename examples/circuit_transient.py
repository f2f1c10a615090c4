"""End-to-end driver: transient simulation of a nonlinear power grid.

Backward-Euler + Newton-Raphson; the GLU plan is built once and ~hundreds
of refactorizations run on the fixed pattern — the paper's target workload.

  PYTHONPATH=src python examples/circuit_transient.py
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np

from repro.circuit import rc_grid_circuit, transient
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ckt = rc_grid_circuit(10, 10, with_diodes=True, seed=0)
    print(f"grid 10x10: {ckt.n} nodes, {len(ckt.resistors)} R, "
          f"{len(ckt.capacitors)} C, {len(ckt.diodes)} diodes, "
          f"{len(ckt.isources)} switching loads")
    res = transient(ckt, t_end=0.10, dt=0.002)
    print(f"steps={len(res.times)}  newton_iters={res.newton_iters.sum()}  "
          f"factorizations={res.n_factorizations}")
    print(f"symbolic setup {res.setup_seconds:.2f}s (once)  "
          f"numeric loop {res.solve_seconds:.2f}s "
          f"({res.solve_seconds / res.n_factorizations * 1e3:.1f} ms/refactorize+solve)")
    print(f"max Newton residual {res.max_residual:.2e}")
    vmin, vmax = res.voltages.min(), res.voltages.max()
    print(f"voltage envelope [{vmin:.3f}, {vmax:.3f}] V")
    assert np.isfinite(res.voltages).all()


if __name__ == "__main__":
    main()
