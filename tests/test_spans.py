"""The solver's profiler spans, set-up record and program names.

A small ladder runs GLU construction, ``factorize`` + ``solve(refine=3)``
and ``refactorize_solve`` under ``jax.profiler.trace``; the ``.xplane.pb``
it writes is read back with ``ProfileData``.  The host plane then holds the
``glu.*`` spans and one ``PjitFunction(<name>)`` event per program launch,
on one clock, so the spans' nesting and the programs each call launched can
be read off it.
"""
import glob
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import GLU, PlanCache
from repro.core.factorize import _build_factorize_runner
from repro.core.triangular import (
    _build_trisolve_runner,
    _residual_berr,
    _residual_berr_batched,
    _residual_berr_multi,
)
from repro.kernels import ops as kops
from repro.sparse.gen import rc_ladder
from repro.spans import named, timed

PROGRAM = "PjitFunction("
STAGES = ["ordering", "permute", "symbolic", "levelize", "plan", "total"]


def _read_host(path):
    """``(spans, programs)``: the ``glu.*`` spans and the outermost
    program-launch events of the host plane, as ``(name, start, end)``."""
    from jax.profiler import ProfileData

    spans, launches = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith("glu."):
                    spans.append(iv)
                elif ev.name.startswith(PROGRAM):
                    launches.append(iv)
    # a launch is recorded as two nested events; keep the outer one
    programs, reach = [], -1.0
    for name, s, e in sorted(launches, key=lambda x: (x[1], -x[2])):
        if s >= reach:
            programs.append((name[len(PROGRAM):-1], s, e))
            reach = e
    return sorted(spans, key=lambda x: x[1]), programs


def _parent(span, spans):
    """Name of the innermost other span enclosing ``span``, or None."""
    _, s, e = span
    outer = [o for o in spans if o is not span and o[1] <= s and e <= o[2]]
    return min(outer, key=lambda o: o[2] - o[1])[0] if outer else None


def _inside(events, span):
    return [ev for ev in events if span[1] <= ev[1] and ev[2] <= span[2]]


def _calls(glu, A, b, V, B):
    glu.factorize(A.data)
    x = glu.solve(b, refine=3)
    info = glu.solve_info
    X = glu.refactorize_solve(V, B, refine=3)
    return x, info, X, glu.solve_info


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same calls without and then with the profiler on (the first run
    also compiles, so the traced one launches cached programs only)."""
    A = rc_ladder(64)
    rng = np.random.default_rng(3)
    b = rng.standard_normal(A.n)
    V = np.stack([A.data, 1.02 * A.data])
    B = rng.standard_normal((2, A.n))
    kw = dict(dtype=jnp.float64, plan_cache=None, refine_tol=0.0)
    plain = _calls(GLU(A, **kw), A, b, V, B)
    out = tmp_path_factory.mktemp("glu_trace")
    with jax.profiler.trace(str(out)):
        glu = GLU(A, **kw)
        result = _calls(glu, A, b, V, B)
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans, programs = _read_host(path)
    return glu, plain, result, spans, programs


def test_span_names_nesting_and_count_per_call(traced):
    _, _, _, spans, _ = traced
    got = Counter((s[0], _parent(s, spans)) for s in spans)
    want = Counter({
        ("glu.setup", None): 1,
        ("glu.scaling", "glu.setup"): 1,
        ("glu.plan", "glu.setup"): 1,
        ("glu.executor_build", "glu.setup"): 1,
        ("glu.factorize", None): 1,
        ("glu.solve", None): 1,
        ("glu.refactorize_solve", None): 1,
        ("glu.factorize_batched", "glu.refactorize_solve"): 1,
        ("glu.solve_batched", "glu.refactorize_solve"): 1,
        ("glu.refine", "glu.solve"): 1,
        ("glu.refine", "glu.solve_batched"): 1,
        ("glu.sync", "glu.refine"): 4,          # two per refined solve
    })
    for stage in STAGES[:-1]:
        want[(f"glu.plan.{stage}", "glu.plan")] = 1
    for call in ("glu.factorize", "glu.factorize_batched"):
        want[("glu.prep", call)] = want[("glu.h2d", call)] = 1
    for call in ("glu.solve", "glu.solve_batched"):
        for name in ("glu.prep", "glu.h2d", "glu.d2h", "glu.post"):
            want[(name, call)] = 1
    assert got == want


def test_each_call_launches_what_its_counters_say(traced):
    """Program launches inside each call's span match ``n_dispatches`` and
    ``solve_dispatches``, by name, with the profiler on."""
    _, _, (_, info, _, info_b), spans, programs = traced

    def launched(name):
        span, = [s for s in spans if s[0] == name]
        return Counter(p[0] for p in _inside(programs, span))

    assert launched("glu.factorize") == {"glu_factorize": 1}
    assert info["n_dispatches"] == 1
    refine = {"glu_trisolve": 4, "glu_residual": 4, "glu_correct": 3,
              "greater": 3, "add": 3, "abs": 1}
    assert launched("glu.solve") == refine | {"convert_element_type": 1}
    assert info["solve_dispatches"] == sum(launched("glu.solve").values())
    assert launched("glu.factorize_batched") == {"glu_factorize": 1}
    assert info_b["n_dispatches"] == 1
    batched = refine | {"convert_element_type": 1, "broadcast_in_dim": 1}
    assert launched("glu.solve_batched") == batched
    assert info_b["solve_dispatches"] == sum(batched.values())
    # the deferred diagnostics run under their own name, outside the calls
    assert "glu_factor_stats" in {p[0] for p in programs}


def test_profiler_changes_no_result(traced):
    _, (x0, info0, X0, infob0), (x1, info1, X1, infob1), _, _ = traced
    np.testing.assert_array_equal(x0, x1)
    np.testing.assert_array_equal(X0, X1)
    for a, b in ((info0, info1), (infob0, infob1)):
        assert a["host_syncs"] == b["host_syncs"] == 2
        np.testing.assert_array_equal(a["refine_iters"], 3)
        np.testing.assert_array_equal(b["refine_iters"], 3)
        assert a["solve_dispatches"] == b["solve_dispatches"]


def test_setup_seconds_on_a_plan_cache_miss(traced):
    glu = traced[0]
    build = glu.symbolic_plan.build_seconds
    assert list(build) == STAGES
    assert all(v >= 0 for v in build.values())
    assert build["total"] >= sum(build[k] for k in STAGES[:-1])
    assert list(glu.setup_seconds) == ["scaling", "plan", "executor",
                                       "verify"]
    assert not glu.plan_from_cache
    assert glu.setup_seconds["plan"] == build["total"]
    assert glu.setup_seconds["scaling"] > 0
    assert glu.setup_seconds["executor"] > 0
    assert glu.setup_seconds["verify"] == 0.0


def test_setup_seconds_on_a_plan_cache_hit_and_from_plan():
    A = rc_ladder(48)
    cache = PlanCache()
    first = GLU(A, dtype=jnp.float64, plan_cache=cache)
    again = GLU(A, dtype=jnp.float64, plan_cache=cache)
    assert again.plan_from_cache and again.setup_seconds["plan"] == 0.0
    assert first.setup_seconds["plan"] == \
        first.symbolic_plan.build_seconds["total"]
    reuse = GLU.from_plan(first.symbolic_plan, A, dtype=jnp.float64)
    assert reuse.setup_seconds["plan"] == 0.0
    assert reuse.setup_seconds["scaling"] > 0
    assert reuse.setup_seconds["executor"] > 0


def test_verify_is_timed_when_on():
    glu = GLU(rc_ladder(48), dtype=jnp.float64, plan_cache=None,
              verify="plan")
    assert glu.setup_seconds["verify"] > 0


def test_timed_writes_host_seconds():
    into = {}
    with timed("glu.test", into, "stage"):
        pass
    assert list(into) == ["stage"] and into["stage"] >= 0


@pytest.mark.parametrize("name, program", [
    ("glu_factor_stats", kops.factor_stats),
    ("glu_factor_stats", kops.factor_stats_batched),
    ("glu_factor_stats", kops.factor_stats_planar),
    ("glu_factor_stats", kops.factor_stats_planar_batched),
    ("glu_correct", kops.masked_correction),
    ("glu_residual", _residual_berr),
    ("glu_residual", _residual_berr_batched),
    ("glu_residual", _residual_berr_multi),
    ("glu_trisolve", _build_trisolve_runner("single")),
    ("glu_trisolve", _build_trisolve_runner("batched", planar=True)),
    ("glu_trisolve", _build_trisolve_runner("multi")),
])
def test_every_program_family_has_its_fixed_name(name, program):
    assert program.__name__ == name


@pytest.mark.parametrize("entry, batched, robust", [
    ("scatter", False, False), ("filled", False, False),
    ("scatter", True, False), ("scatter", False, True),
])
def test_factorize_runners_lower_as_glu_factorize(entry, batched, robust):
    runner = _build_factorize_runner(
        (), entry=entry, batched=batched, robust=robust, interpret=True,
        use_pallas=False, nnz=4, dtype=jnp.float64)
    a = jnp.zeros((2, 4) if batched else (4,))
    eps = jnp.asarray(1e-12) if robust else None
    text = runner.lower(a, jnp.arange(4), (), (), eps).as_text()
    assert "@jit_glu_factorize" in text.splitlines()[0]


def test_named_leaves_the_function_alone():
    def body(x):
        return x + 1

    program = named("glu_test", body)
    assert body.__name__ == "body" and program.__name__ == "glu_test"
    assert program(1) == 2
    assert "@jit_glu_test" in jax.jit(program).lower(1.0).as_text()
