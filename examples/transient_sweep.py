"""Batched parameter sweep: N perturbed copies of a circuit, one plan.

Monte-Carlo / process-corner analysis: every copy shares the sparsity
pattern, so the GLU symbolic plan is built once and each lockstep Newton
iterate factorizes ALL copies with a single batched device dispatch per
level-group (``GLU.refactorize_solve``).

  PYTHONPATH=src python examples/transient_sweep.py
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np

from repro.circuit import rc_grid_circuit, transient_sweep
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ckt = rc_grid_circuit(8, 8, with_diodes=True, seed=0)
    scales = np.linspace(0.8, 1.2, 9)   # ±20% conductance corners
    print(f"grid 8x8: {ckt.n} nodes, sweeping {len(scales)} corners "
          f"{scales.round(2).tolist()}")
    res = transient_sweep(ckt, t_end=0.05, dt=0.002, scales=scales)
    print(f"steps={len(res.times)}  lockstep newton_iters={res.newton_iters.sum()}  "
          f"batched factorizations={res.n_batched_factorizations} "
          f"(x{len(scales)} matrices each)")
    print(f"symbolic setup {res.setup_seconds:.2f}s (once)  "
          f"numeric loop {res.solve_seconds:.2f}s")
    print(f"max Newton residual {res.max_residual:.2e}")
    v_final = res.voltages[:, -1, :]
    spread = v_final.max(axis=0) - v_final.min(axis=0)
    print(f"corner-to-corner final-voltage spread: "
          f"max {spread.max():.4f} V, mean {spread.mean():.4f} V")
    assert np.isfinite(res.voltages).all()


if __name__ == "__main__":
    main()
