"""Host spans and stable program names, for profiling the solver.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
being collected (``jax.profiler.trace`` / ``start_trace``) it is written into
that trace on the same clock as the device planes; otherwise opening it is
one cheap check.  Spans wrap host work only and never wait for the device.
Span names are fixed strings (``glu.<layer>``); README.md lists them.

Every jitted program the solver launches is compiled under a fixed name
(:func:`named`), so a trace's ``XLA Modules`` line and its host
``PjitFunction(<name>)`` events read ``glu_factorize``, ``glu_trisolve``,
``glu_residual``, ``glu_correct`` and ``glu_factor_stats`` whatever the
Python function behind them is called.
"""
from __future__ import annotations

import functools
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

__all__ = ["named", "span", "timed"]

span = TraceAnnotation
"""``with span("glu.solve"): ...`` opens one host span."""


@contextmanager
def timed(name: str, into: dict, key: str):
    """Open the span ``name`` and write its host seconds into ``into[key]``."""
    t0 = time.perf_counter()
    with TraceAnnotation(name):
        yield
    into[key] = time.perf_counter() - t0


def named(name: str, fn):
    """``fn`` under ``name``, which ``jax.jit`` gives the program it compiles
    (``jit_<name>``); ``fn`` itself is left as it is."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return program
