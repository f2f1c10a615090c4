"""Matrix and value helpers of the benchmark.

A configuration names its generator: ``make_matrix`` runs ``make(**args)``
of ``bench/generators/<generator>.py``, which returns ``(n, (indptr,
indices, data), Netlist)``; a new matrix class is one added file there.
``circuit_jacobian`` and ``rc_ladder`` are frozen copies of the program's
synthetic circuit-matrix generators as they stood when the benchmark was
defined, so that a later change to the program's own copies cannot change
what the benchmark measures.  Generators return plain CSC arrays: the
benchmark hands the program only the matrix, never anything the program
built.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


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


def make_matrix(config: dict, bench: Path = BENCH):
    """The configuration's matrix and netlist:
    ``(n, (indptr, indices, data), netlist)``, made by ``make(**args)`` of
    ``<bench>/generators/<config["generator"]>.py``."""
    path = bench / "generators" / f"{config['generator']}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_generator_" + config["generator"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    n, csc, net = mod.make(**config["args"])
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
