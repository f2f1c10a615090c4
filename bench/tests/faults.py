"""Faults planted under the timed path, for the test that ``correct``
comes out false.  Each patches ``GLU`` in this process only."""
from __future__ import annotations

import numpy as np

from repro.core import GLU


def state_unchanged(mp):
    """Refactorization keeps the first factors: every later step solves
    with the values of the first one."""
    fact, fact_b = GLU.factorize, GLU.factorize_batched

    def factorize(self, a_data=None):
        return fact(self, a_data) if self._vals is None else self

    def factorize_batched(self, a_data_batch):
        if self._vals_batch is None:
            return fact_b(self, a_data_batch)
        return self

    mp.setattr(GLU, "factorize", factorize)
    mp.setattr(GLU, "factorize_batched", factorize_batched)


def answer_altered(mp):
    """One entry of each answer comes back off by one part in a million."""
    solve, rs = GLU.solve, GLU.refactorize_solve

    def bump(x):
        x = np.array(x)
        x[..., 0] *= 1.0 + 1e-6
        return x

    mp.setattr(GLU, "solve", lambda self, *a, **k: bump(solve(self, *a, **k)))
    mp.setattr(GLU, "refactorize_solve",
               lambda self, *a, **k: bump(rs(self, *a, **k)))


def half_batch(mp):
    """Only the first half of a sweep's batch is solved; its answers stand
    in for the rest."""
    rs = GLU.refactorize_solve

    def refactorize_solve(self, vals, rhs, *a, **k):
        h = len(vals) // 2
        x = rs(self, vals[:h], rhs[:h], *a, **k)
        return np.concatenate([x, x])[: len(vals)]

    mp.setattr(GLU, "refactorize_solve", refactorize_solve)


def no_exchange(mp):
    """A sharded sweep returns no shard's answers but the first one's: the
    host reads the first chip's rows in place of every chip's."""
    rs = GLU.refactorize_solve

    def refactorize_solve(self, vals, rhs, *a, **k):
        x = rs(self, vals, rhs, *a, **k)
        per = len(x) // self.n_devices
        return np.concatenate([x[:per]] * self.n_devices)

    mp.setattr(GLU, "refactorize_solve", refactorize_solve)


FAULTS = {"state_unchanged": state_unchanged,
          "answer_altered": answer_altered,
          "half_batch": half_batch,
          "no_exchange": no_exchange}
