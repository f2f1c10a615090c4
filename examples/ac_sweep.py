"""AC small-signal frequency sweep: one complex plan, all points batched.

The sweep factorizes A(w) = G + jwC at every frequency on ONE symbolic
plan: the DC operating point is found with the real-valued Newton loop,
then a single batched complex128 factorize+solve covers all F points in
lockstep (``GLU.refactorize_solve`` under the hood).

  PYTHONPATH=src python examples/ac_sweep.py
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np

from repro.circuit import rc_grid_circuit, ac_sweep
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ckt = rc_grid_circuit(8, 8, with_diodes=True, seed=0)
    ckt.add_ac_current_source(1, 0, 1.0)   # 1A small-signal probe at node 1
    freqs = np.logspace(0, 5, 21)
    print(f"grid 8x8: {ckt.n} nodes, sweeping {len(freqs)} frequency points "
          f"[{freqs[0]:.0f} Hz .. {freqs[-1]:.0f} Hz]")
    res = ac_sweep(ckt, freqs)
    print(f"operating point found in {res.op_newton_iters} Newton iters; "
          f"batched complex factorizations: {res.n_batched_factorizations}")
    print(f"setup {res.setup_seconds:.2f}s (op point + one complex plan)  "
          f"sweep solve {res.solve_seconds:.3f}s "
          f"({res.solve_seconds / len(freqs) * 1e3:.2f} ms/point)")
    print(f"worst componentwise backward error {res.max_backward_error:.2e}")
    mag = np.abs(res.voltages[:, 0])
    print("probe-node |V(f)|:")
    for f, m in zip(freqs[::4], mag[::4]):
        print(f"  {f:>9.1f} Hz  {m:.4e} V")
    assert res.max_backward_error < 1e-10
    assert (np.diff(mag) <= 1e-12).all(), "RC grid must be low-pass at the probe"


if __name__ == "__main__":
    main()
