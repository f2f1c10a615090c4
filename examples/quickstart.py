"""Quickstart: factorize a circuit matrix with GLU3.0 and solve Ax = b.

  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import GLU
from repro.sparse import circuit_jacobian


def main():
    enable_compile_cache()
    # a 2000-node circuit-style sparse matrix (structurally symmetric-ish,
    # diagonally dominant — what MNA assembly produces)
    A = circuit_jacobian(2000, avg_degree=4.0, seed=0)
    b = np.random.default_rng(0).normal(size=A.n)

    # plan once: MC64 -> fill-reducing ordering -> symbolic fill-in ->
    # relaxed dependency detection (paper Alg. 4) -> levelization -> plan
    solver = GLU(A, dtype=jnp.float64)
    print(f"n={A.n}  nnz(A)={A.nnz}  nnz(L+U)={solver.nnz_filled}  "
          f"levels={solver.num_levels}")

    # numeric factorization on device (level-parallel, scan-fused)
    solver.factorize()
    x = solver.solve(b)
    print(f"residual ||Ax-b||_inf / ||b||_inf = {solver.residual(b, x):.2e}")

    # the SPICE pattern: REfactorize new values on the same pattern — no
    # symbolic work, this is the loop GLU3.0 accelerates
    for it in range(3):
        new_vals = np.asarray(A.data) * (1.0 + 0.1 * it)
        solver.factorize(new_vals)
        x = solver.solve(b)
        print(f"refactorization {it}: residual scale-invariant check "
              f"{np.abs(A.to_scipy() @ (x * (1.0 + 0.1 * it)) - b).max():.2e}")


if __name__ == "__main__":
    main()
