"""Static audit of the fused whole-schedule programs.

Walks the jaxpr of the executor's single-dispatch runners and asserts the
properties the performance story rests on:

* **zero host callbacks** — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` (or infeed/outfeed) primitive anywhere in the traced
  program, so a factorization never synchronises with the host mid-flight;
* **donation contract** — the factorize ``entry="filled"`` runner donates
  its value buffer (argument 0), the trisolve runner donates NOTHING
  (the caller retains the factors and the rhs; donation there was the PR 5
  use-after-free bug).  The audit reads the ``tf.aliasing_output`` /
  ``jax.buffer_donor`` markers off the lowered StableHLO, i.e. what XLA
  will actually do, not what the Python wrapper asked for;
* **one dispatch** — the whole schedule is a single jitted callable
  (``jit_schedule=True``), so a (re)factorization or solve is one device
  program launch.

What this does NOT guarantee: numeric correctness (that is
``verify_plan``/``verify_executor``'s job), compile-cache behaviour across
distinct plans, or device-side performance of the lowered program.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from .report import VerifyReport

__all__ = ["audit_factorize", "audit_trisolve", "CALLBACK_PRIMITIVES"]

# primitive names that imply a host round-trip inside the program
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
})

_DONOR_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def _iter_subjaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def collect_primitives(jaxpr) -> set:
    """Every primitive name reachable from ``jaxpr`` (sub-jaxprs included)."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            seen.add(eqn.primitive.name)
            stack.extend(_iter_subjaxprs(eqn.params))
    return seen


def _audit_traced(runner, args, *, name: str, expect_donated: int,
                  rep: VerifyReport) -> None:
    rep.ran(f"audit_{name}")
    closed = jax.make_jaxpr(runner)(*args)
    prims = collect_primitives(closed.jaxpr)
    hits = sorted(prims & CALLBACK_PRIMITIVES)
    if hits:
        rep.add("AUDIT_CALLBACK",
                f"{name} runner contains host callback primitive(s) "
                f"{hits}", runner=name)
    text = runner.lower(*args).as_text()
    donors = sum(text.count(m) for m in _DONOR_MARKERS)
    if donors != expect_donated:
        rep.add("AUDIT_DONATION",
                f"{name} runner marks {donors} donated buffer(s), "
                f"contract requires {expect_donated}",
                runner=name, donors=donors)


def audit_factorize(fact, entry: str = "filled") -> VerifyReport:
    """Audit a :class:`~repro.core.factorize.JaxFactorizer`'s fused runner.

    ``entry="filled"`` must donate exactly its value buffer; the
    ``"scatter"`` entry takes the caller's (retained) A values and donates
    nothing.
    """
    rep = VerifyReport()
    if not fact.jit_schedule:
        rep.ran("audit_factorize")
        rep.add("AUDIT_DISPATCH",
                "jit_schedule=False: factorization issues one dispatch per "
                f"group ({fact.n_groups} groups), not one total")
        return rep
    runner = fact._runner_for(entry, batched=False, shard=None)
    if entry == "filled":
        a = jnp.zeros(fact.layout.storage_shape(fact.nnz),
                      dtype=fact.storage_dtype)
        expect = 1
    else:
        a = jnp.zeros((len(np.asarray(fact._a_scatter)),), dtype=fact.dtype)
        expect = 0
    robust = fact.static_pivot is not None
    eps = (jnp.asarray(fact.static_pivot, dtype=fact.storage_dtype)
           if robust else None)
    _audit_traced(
        runner,
        (a, fact._a_scatter, fact._group_arrays, fact._group_diags, eps),
        name="factorize", expect_donated=expect, rep=rep)
    return rep


def audit_trisolve(solver, dtype=None) -> VerifyReport:
    """Audit a :class:`~repro.core.triangular.JaxTriangularSolver`'s fused
    full-schedule runner.  The trisolve contract is ZERO donated buffers:
    the caller retains both the factor values and the right-hand side."""
    rep = VerifyReport()
    if not solver.jit_schedule:
        rep.ran("audit_trisolve")
        fwd, bwd = solver._full_schedule
        rep.add("AUDIT_DISPATCH",
                "jit_schedule=False: a solve issues one dispatch per level "
                f"group ({len(fwd) + len(bwd)} groups), not one total")
        return rep
    planar = solver._planar
    runner = solver._runner("single", "full")
    nnz, n = solver.plan.nnz, solver.plan.n
    if planar:
        vals = jnp.zeros((nnz, 2), dtype=dtype or jnp.float64)
        b = jnp.zeros(n, dtype=jnp.complex128 if vals.dtype == jnp.float64
                      else jnp.complex64)
    else:
        vals = jnp.zeros(nnz, dtype=dtype or jnp.float64)
        b = jnp.zeros(n, dtype=vals.dtype)
    fwd, bwd = solver._full_schedule
    _audit_traced(runner, (vals, b, tuple(fwd), tuple(bwd), solver._tail),
                  name="trisolve", expect_donated=0, rep=rep)
    return rep
