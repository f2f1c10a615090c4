"""RC ladder conductance matrix: tridiagonal, a leak on every node."""
import numpy as np

from gen import Netlist, csc_from_coo


def make(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    g = rng.uniform(0.5, 2.0, size=n - 1)
    i = np.arange(n - 1)
    rows = np.concatenate([np.stack([i, i + 1, i, i + 1], 1).ravel(),
                           np.arange(n)])
    cols = np.concatenate([np.stack([i, i + 1, i + 1, i], 1).ravel(),
                           np.arange(n)])
    vals = np.concatenate([np.stack([g, g, -g, -g], 1).ravel(),
                           np.full(n, 1e-2)])
    net = Netlist(n, np.concatenate([i, i + 1]), np.concatenate([i + 1, i]),
                  np.concatenate([-g, -g]), np.concatenate([i, i]), n - 1,
                  1e-2)
    return n, csc_from_coo(n, rows, cols, vals), net
