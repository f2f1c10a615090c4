"""Power-grid mesh conductance matrix: an ``nx`` x ``ny`` resistor mesh
with a conductance in [0.5, 2] on every edge and a 1e-3 leak on every node
(``n`` = ``nx * ny``, stated so that the configuration names its size)."""
import numpy as np

from gen import Netlist, csc_from_coo


def make(n: int, nx: int, ny: int, seed: int = 0):
    if n != nx * ny:
        raise ValueError(f"n={n} is not nx*ny={nx * ny}")
    rng = np.random.default_rng(seed)
    node = np.arange(n).reshape(ny, nx)
    gh = rng.uniform(0.5, 2.0, size=(ny, nx - 1))
    gv = rng.uniform(0.5, 2.0, size=(ny - 1, nx))
    # horizontal edges row by row, then vertical ones: one branch each
    a = np.concatenate([node[:, :-1].ravel(), node[:-1, :].ravel()])
    b = np.concatenate([node[:, 1:].ravel(), node[1:, :].ravel()])
    g = np.concatenate([gh.ravel(), gv.ravel()])
    rows = np.concatenate([np.stack([a, b, a, b], 1).ravel(), np.arange(n)])
    cols = np.concatenate([np.stack([a, b, b, a], 1).ravel(), np.arange(n)])
    vals = np.concatenate([np.stack([g, g, -g, -g], 1).ravel(),
                           np.full(n, 1e-3)])
    e = np.arange(len(g))
    net = Netlist(n, np.concatenate([a, b]), np.concatenate([b, a]),
                  np.concatenate([-g, -g]), np.concatenate([e, e]), len(g),
                  1e-3)
    return n, csc_from_coo(n, rows, cols, vals), net
