"""Matrix and value generators of the benchmark.

Frozen copies of the program's synthetic circuit-matrix generators
(``circuit_jacobian`` and ``rc_ladder`` as they stood when the benchmark was
defined), so that a later change to the program's own copies cannot change
what the benchmark measures.  They return plain CSC arrays: the benchmark
hands the program only the matrix, never anything the program built.
"""
from __future__ import annotations

import numpy as np


def csc_from_coo(n, rows, cols, vals):
    """Sum duplicates and compress by column: ``(indptr, indices, data)``
    with row indices sorted within each column."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    order = np.lexsort((rows, cols))
    rows, cols, vals = rows[order], cols[order], vals[order]
    key = cols * n + rows
    uniq, inv = np.unique(key, return_inverse=True)
    data = np.zeros(len(uniq), dtype=np.float64)
    np.add.at(data, inv, vals)
    indices = (uniq % n).astype(np.int32)
    ucols = uniq // n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, ucols + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return indptr, indices, data


class Netlist:
    """A generated matrix with the branches it was stamped from.

    Every off-diagonal entry belongs to one branch (a device between two
    nodes, both of whose entries it stamps); the diagonal is the node's leak
    plus the row sum of the magnitudes of its off-diagonal entries, as both
    generators build it.  ``restamp`` gives the values of the same pattern
    after each branch's conductance is scaled by its own factor: the
    diagonal follows, so every row keeps its leak as its margin of
    dominance, as a SPICE Newton update that re-stamps device conductances
    does."""

    def __init__(self, n, rows, cols, vals, branch, n_branches, leak):
        self.n = n
        self.n_branches = int(n_branches)
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        diag = np.arange(n, dtype=np.int64)
        allr = np.concatenate([rows, diag])
        allc = np.concatenate([cols, diag])
        key = allc * n + allr
        uniq, inv = np.unique(key, return_inverse=True)
        self.nnz = len(uniq)
        self._off = inv[: len(rows)]
        self._diag = inv[len(rows):]
        self._rows = rows
        self._vals = np.asarray(vals, dtype=np.float64)
        self._branch = np.asarray(branch, dtype=np.int64)
        self._leak = np.broadcast_to(np.asarray(leak, dtype=np.float64), (n,))
        self.indptr, self.indices, self.data = csc_from_coo(
            n, allr, allc, np.ones(len(allr)))

    def restamp(self, factors):
        """Values (in CSC order) with branch ``b``'s conductance scaled by
        ``factors[b]``."""
        v = self._vals * np.asarray(factors, dtype=np.float64)[self._branch]
        out = np.bincount(self._off, weights=v, minlength=self.nnz)
        diag = self._leak + np.bincount(self._rows, weights=np.abs(v),
                                        minlength=self.n)
        out[self._diag] += diag
        return out


def rc_ladder(n: int, seed: int = 0):
    """RC ladder conductance matrix: tridiagonal, a leak on every node."""
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


def circuit_jacobian(n: int, avg_degree: float = 4.0, n_rails: int = 0,
                     rail_fanout: int = 64, asym: float = 0.1,
                     pattern_asym: float = 0.0, seed: int = 0):
    """Random circuit-Jacobian-like matrix: a mostly symmetric random
    coupling pattern with ``asym`` value asymmetry, ``pattern_asym``
    one-sided entries, ``n_rails`` high-degree nodes, and a diagonal of
    row-sum dominance plus a 0.5 leak."""
    rng = np.random.default_rng(seed)
    m = int(n * avg_degree / 2)
    a = rng.integers(0, n, size=m)
    b = rng.integers(0, n, size=m)
    keep = a != b
    a, b = a[keep], b[keep]
    g = rng.uniform(0.1, 1.0, size=len(a))
    if pattern_asym > 0:
        one_sided = rng.uniform(size=len(a)) < pattern_asym
    else:
        one_sided = np.zeros(len(a), dtype=bool)
    two = ~one_sided
    rows = [a, b[two]]
    cols = [b, a[two]]
    vals = [-g, -g[two] * (1.0 - asym * rng.uniform(0, 1, size=two.sum()))]
    branch = [np.arange(len(a)), np.flatnonzero(two)]
    n_branches = len(a)
    for _ in range(n_rails):
        node = rng.integers(0, n)
        targets = rng.choice(n, size=min(rail_fanout, n - 1), replace=False)
        targets = targets[targets != node]
        gr = rng.uniform(0.1, 1.0, size=len(targets))
        rows.extend([np.full(len(targets), node), targets])
        cols.extend([targets, np.full(len(targets), node)])
        vals.extend([-gr, -gr])
        ids = n_branches + np.arange(len(targets))
        branch.extend([ids, ids])
        n_branches += len(targets)
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = np.concatenate(vals)
    net = Netlist(n, rows, cols, vals, np.concatenate(branch), n_branches,
                  0.5)
    diag = np.full(n, 0.5)
    np.add.at(diag, rows, np.abs(vals))
    rows = np.concatenate([rows, np.arange(n)])
    cols = np.concatenate([cols, np.arange(n)])
    vals = np.concatenate([vals, diag])
    return n, csc_from_coo(n, rows, cols, vals), net


GENERATORS = {"circuit_jacobian": circuit_jacobian, "rc_ladder": rc_ladder}


def make_matrix(config: dict):
    """The configuration's matrix and netlist:
    ``(n, (indptr, indices, data), netlist)``."""
    n, csc, net = GENERATORS[config["generator"]](**config["args"])
    if not (np.array_equal(csc[0], net.indptr)
            and np.array_equal(csc[1], net.indices)):
        raise AssertionError("the netlist's pattern is not the matrix's")
    return n, csc, net


def value_pool(net: Netlist, rng, count: int, spread: float):
    """``count`` value vectors of the netlist's pattern, each re-stamped
    with every branch's conductance scaled by its own factor in
    ``[1 - spread, 1 + spread]``."""
    f = 1.0 + spread * rng.uniform(-1.0, 1.0, (count, net.n_branches))
    return np.stack([net.restamp(fk) for fk in f])
