"""The one traffic generator and loop; each mix is a data file.

A mix file (``bench/mixes/<name>.json``) gives its ``kind`` and parameters:

* ``newton``: the inner loop of a SPICE transient analysis.  Each step
  refactorizes with new values and solves one right-hand side with
  ``refine`` sweeps of iterative refinement: ``glu.factorize(v)`` then
  ``glu.solve(b, refine=...)``, which returns host numpy.
* ``sweep``: Monte Carlo or corner sweeps.  Each call is
  ``glu.refactorize_solve(V, B, refine=...)`` over ``batch`` scenarios.

Both are closed loops with one caller.  Values are the configuration's
netlist re-stamped with each branch's conductance scaled by a factor of its
own in ``[1 - spread, 1 + spread]`` (``gen.value_pool``), and right-hand
sides are standard normal; ``pool`` of them (steps or batches) are drawn
from the seed before the window and cycled.  Every seed
draws the same sizes, so seeds change the numbers and never the work.
"""
from __future__ import annotations

import contextlib

import numpy as np

from gen import value_pool

KINDS = ("newton", "sweep")


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _no_span(name):
    return contextlib.nullcontext()


class Traffic:
    def __init__(self, mix: dict, net, seed: int):
        if mix["kind"] not in KINDS:
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        self.mix = mix
        self.kind = mix["kind"]
        self.refine = int(mix["refine"])
        self.batch = int(mix.get("batch", 1))
        rng = np.random.default_rng([seed, 0])
        pool = int(mix["pool"])
        rows = pool * self.batch
        n = net.n
        self.values = value_pool(net, rng, rows, float(mix["spread"]))
        self.rhs = rng.standard_normal((rows, n))
        if self.kind == "sweep":
            self.values = self.values.reshape(pool, self.batch, -1)
            self.rhs = self.rhs.reshape(pool, self.batch, n)
        self.pool = pool
        self.outputs = []              # (pool index, solution(s)) per call
        self.refine_iters = []         # per traced Newton step
        self.solve_info = []           # glu.solve_info per traced step

    def step(self, glu, i: int, traced: bool = False):
        """Run call ``i`` and keep its answer.  ``traced`` wraps the call
        in the benchmark's host spans, waits for a Newton step's factorize
        inside its own span (the one sync the traced run adds) and, after
        a Newton step, keeps a copy of ``glu.solve_info`` and the step's
        refinement sweeps from it.  A sweep call reads nothing inside the
        window: ``run.py`` reads its ``solve_info`` once the window has
        closed."""
        k = i % self.pool
        span = _span if traced else _no_span
        with span("bench.step"):
            if self.kind == "newton":
                with span("bench.factorize"):
                    glu.factorize(self.values[k])
                    if traced:
                        import jax

                        jax.block_until_ready(glu.factorized_values())
                with span("bench.solve"):
                    x = glu.solve(self.rhs[k], refine=self.refine)
            else:
                with span("bench.sweep"):
                    x = glu.refactorize_solve(self.values[k], self.rhs[k],
                                              refine=self.refine)
        self.outputs.append((k, x))
        if traced and self.kind == "newton":
            info = glu.solve_info          # a new dict at every read
            self.solve_info.append(info)
            self.refine_iters.append(info["refine_iters"])
        return x

    def answers(self):
        """Every answer of the window as ``(key, values, rhs, solution)``;
        answers with the same ``key`` share their values."""
        out = []
        for k, x in self.outputs:
            if self.kind == "newton":
                out.append((k, self.values[k], self.rhs[k], x))
            else:
                for j in range(self.batch):
                    xj = x[j] if x is not None and len(x) > j else None
                    out.append(((k, j), self.values[k][j], self.rhs[k][j], xj))
        return out

    def matrices_per_call(self) -> int:
        return self.batch if self.kind == "sweep" else 1
