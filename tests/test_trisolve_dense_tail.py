"""Dense-tail triangular solve: the trailing block [c*, n) that the
factorizer finishes as one dense LU is solved by dense substitution on the
factored block, gathered from ``vals``, not walked level by level.

Contracts under test:

* the solve matches the sequential ``trisolve_numpy`` oracle and scipy's
  ``splu`` to 1e-12;
* the fused program and the per-group (``jit_schedule=False``) path are
  bitwise equal, single, batched and many-RHS, and agree with the plain
  level walk of the same factors;
* a pruned sparse-RHS solve is bitwise the full one on its reach, whether
  the reach enters the tail (the whole dense step runs) or not (it is left
  out);
* ``solve_info`` reports the dense size and the padded gather/scatter
  entries one trisolve walks; a plan without a tail walks what it walked
  before, and a refined solve still launches the same programs;
* the schedule verifier counts the dense step's entries and the tail
  columns' U entries in the first prefix backward level, and flags a
  corrupted map or level.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse.linalg as spla

import jax.numpy as jnp

from repro.analysis import verify_trisolver
from repro.core import (
    GLU,
    JaxFactorizer,
    JaxTriangularSolver,
    build_plan,
    fill_reducing_ordering,
    symbolic_fillin_gp,
)
from repro.core.triangular import trisolve_numpy
from repro.sparse import circuit_jacobian, rc_ladder


@pytest.fixture(scope="module")
def tail_problem():
    """The executor tests' dense pattern: fills to a 110-column tail."""
    A0 = circuit_jacobian(500, avg_degree=4.0, seed=22)
    perm = fill_reducing_ordering(A0, "mindeg")
    A = A0.permute(perm, perm)
    plan = build_plan(symbolic_fillin_gp(A))
    fx = JaxFactorizer(plan, dtype=jnp.float64)
    assert fx.dense_tail_info is not None
    vals = fx.factorize(A.data)
    return A, plan, fx, vals


@pytest.fixture(scope="module")
def tail_glu():
    A = circuit_jacobian(300, avg_degree=4.5, seed=11)
    glu = GLU(A)
    assert glu._factorizer.dense_tail_info is not None
    return A, glu


def _solvers(plan, fx):
    info = fx.dense_tail_info
    fused = JaxTriangularSolver(plan, dense_tail=info)
    legacy = JaxTriangularSolver(plan, fuse_buckets=False, jit_schedule=False,
                                 dense_tail=info)
    return fused, legacy


def _rel(x, ref):
    return np.abs(x - ref).max() / np.abs(ref).max()


def test_matches_numpy_oracle_and_splu(tail_problem):
    A, plan, fx, vals = tail_problem
    fused, _ = _solvers(plan, fx)
    b = np.random.default_rng(0).standard_normal(plan.n)
    x = np.asarray(fused.solve(vals, b))
    assert fused.last_dense_tail == fx.dense_tail_info["size"]
    assert _rel(x, trisolve_numpy(plan, np.asarray(vals), b)) <= 1e-12
    assert _rel(x, spla.splu(A.to_scipy().tocsc()).solve(b)) <= 1e-12


def test_glu_matches_splu(tail_glu):
    A, glu = tail_glu
    glu.factorize()
    b = np.random.default_rng(1).standard_normal(A.n)
    x = glu.solve(b)
    assert glu.solve_info["trisolve_dense_tail"] == \
        glu._factorizer.dense_tail_info["size"]
    assert _rel(x, spla.splu(A.to_scipy().tocsc()).solve(b)) <= 1e-12


def _kind_args(A, plan, fx, vals, kind, rng):
    if kind == "single":
        return "solve", (vals, rng.standard_normal(plan.n))
    if kind == "batched":
        vb = fx.factorize_batched(np.stack([A.data, 0.5 * A.data]))
        return "solve_batched", (vb, rng.standard_normal((2, plan.n)))
    return "solve_multi", (vals, rng.standard_normal((3, plan.n)))


@pytest.mark.parametrize("kind", ["single", "batched", "multi"])
def test_fused_bitwise_equals_per_group(tail_problem, kind):
    A, plan, fx, vals = tail_problem
    fused, legacy = _solvers(plan, fx)
    solve, args = _kind_args(A, plan, fx, vals, kind,
                             np.random.default_rng(2))
    xf = np.asarray(getattr(fused, solve)(*args))
    xl = np.asarray(getattr(legacy, solve)(*args))
    assert xf.tobytes() == xl.tobytes()
    assert fused.last_n_dispatches == 1
    # prefix groups, the rhs copy and the dense step
    fwd, bwd = legacy._full_schedule
    assert legacy.last_n_dispatches == len(fwd) + len(bwd) + 2


@pytest.mark.parametrize("kind", ["single", "batched", "multi"])
def test_matches_level_walk(tail_problem, kind):
    """The same factors solved by the plain level walk (a solver built
    without the tail) agree to rounding."""
    A, plan, fx, vals = tail_problem
    fused, _ = _solvers(plan, fx)
    walk = JaxTriangularSolver(plan)
    solve, args = _kind_args(A, plan, fx, vals, kind,
                             np.random.default_rng(3))
    xf = np.asarray(getattr(fused, solve)(*args))
    xw = np.asarray(getattr(walk, solve)(*args))
    assert fused.last_dense_tail == fx.dense_tail_info["size"]
    assert walk.last_dense_tail == 0
    assert _rel(xf, xw) <= 1e-12


def _prefix_col_outside_tail(plan, c_star):
    for j in range(c_star):
        if plan.fwd_reach([j])[-1] < c_star:
            return j
    pytest.fail("every prefix column's reach enters the tail")


@pytest.mark.parametrize("where", ["tail", "prefix"])
def test_pruned_bitwise_on_reach(tail_problem, where):
    _, plan, fx, vals = tail_problem
    fused, legacy = _solvers(plan, fx)
    c_star = fx.dense_tail_info["c_star"]
    if where == "tail":
        pat = [c_star + 3, plan.n - 2]
    else:
        pat = [_prefix_col_outside_tail(plan, c_star)]
    b = np.zeros(plan.n)
    b[pat] = [1.5, -0.25][: len(pat)]
    _, _, freach, breach = fused.schedule_for_pattern(pat)
    xp = np.asarray(fused.solve(vals, b, rhs_pattern=pat))
    engaged = fused.last_dense_tail
    pruned_entries = fused.last_indexed_entries
    xl = np.asarray(legacy.solve(vals, b, rhs_pattern=pat))
    assert xp.tobytes() == xl.tobytes()
    full = np.asarray(fused.solve(vals, b))
    np.testing.assert_array_equal(xp[breach], full[breach])
    if where == "tail":
        assert freach[0] >= c_star and freach[-1] == plan.n - 1
        assert engaged == fx.dense_tail_info["size"]
    else:
        assert breach[-1] < c_star
        assert engaged == 0
        assert not np.any(full[c_star:])
    assert pruned_entries < fused.last_indexed_entries


def test_glu_pruned_tail_pattern_bitwise(tail_glu):
    A, glu = tail_glu
    glu.factorize()
    c_star = glu._factorizer.dense_tail_info["c_star"]
    # original rows whose permuted position lies in the tail
    rows = np.flatnonzero(glu.row_map >= c_star)[:2]
    b = np.zeros(A.n)
    b[rows] = [1.0, -2.0]
    x_pruned = glu.solve(b, rhs_pattern=rows)
    assert glu.solve_info["trisolve_dense_tail"] > 0
    x_full = glu.solve(b)
    _, _, _, breach = glu._solver.schedule_for_pattern(glu.row_map[rows])
    on = np.isin(glu.col_map, breach)
    np.testing.assert_array_equal(x_pruned[on], x_full[on])


def test_planar_keeps_level_walk(tail_problem):
    A, plan, _, _ = tail_problem
    planar = JaxFactorizer(plan, dtype=jnp.complex128, layout="planar")
    assert planar.dense_tail_info is not None
    solver = JaxTriangularSolver(plan, layout="planar",
                                 dense_tail=planar.dense_tail_info)
    assert solver.dense_tail_info is None and solver._tail is None


def test_solve_info_counters(tail_glu):
    A, glu = tail_glu
    b = np.random.default_rng(4).standard_normal(A.n)
    glu.factorize()
    glu.solve(b)
    info = glu.solve_info
    walked = GLU(A, dense_tail=False).factorize()
    walked.solve(b)
    assert info["trisolve_dense_tail"] == \
        glu._factorizer.dense_tail_info["size"]
    assert walked.solve_info["trisolve_dense_tail"] == 0
    assert 0 < info["trisolve_indexed_entries"] < \
        walked.solve_info["trisolve_indexed_entries"]


def test_solve_info_counters_unchanged_without_tail():
    A = rc_ladder(120, seed=0)
    glu = GLU(A).factorize()
    assert glu._factorizer.dense_tail_info is None
    glu.solve(np.ones(A.n))
    info = glu.solve_info
    assert info["trisolve_dense_tail"] == 0
    fwd, bwd = glu._solver._full_schedule
    walked = (sum(int(np.prod(g[0].shape)) for g in fwd)
              + sum(int(np.prod(g[2].shape)) for g in bwd))
    assert info["trisolve_indexed_entries"] == walked
    off = GLU(A, dense_tail=False).factorize()
    off.solve(np.ones(A.n))
    assert off.solve_info["trisolve_indexed_entries"] == walked


def test_refined_solve_dispatches(tail_glu):
    """A refined Newton-style solve on a tail plan launches the same
    programs as before: one factorize, and 19 on the solve side for
    refine=3 the first time (|A| included)."""
    A, _ = tail_glu
    glu = GLU(A, refine_tol=0.0)
    glu.factorize(A.data * 1.01)
    glu.solve(np.random.default_rng(5).standard_normal(A.n), refine=3)
    info = glu.solve_info
    assert info["refine_iters"] == 3
    assert info["n_dispatches"] == 1
    assert info["solve_dispatches"] == 19


def test_refactorize_solve_single_then_solve(tail_glu):
    """A one-row fused refactorize + solve leaves single factors whose
    later solve repeats it bitwise."""
    A, glu = tail_glu
    rng = np.random.default_rng(6)
    b = rng.standard_normal(A.n)
    x = glu.refactorize_solve(A.data, b, refine=1)
    x2 = glu.solve(b, refine=1)
    assert glu.solve_info["trisolve_dense_tail"] > 0
    np.testing.assert_array_equal(x, x2)


def test_verify_full_passes_on_tail_matrices():
    glu = GLU(circuit_jacobian(300, avg_degree=4.5, seed=11), verify="full")
    assert glu._factorizer.dense_tail_info is not None
    assert "trisolve_dense_tail" in glu.verify_report.checks
    assert glu.verify_report.ok


def test_first_prefix_bwd_level_holds_tail_u_entries(tail_problem):
    """Every U entry of a tail column in a prefix row is applied in the
    first backward level, and no level divides a tail column."""
    _, plan, fx, _ = tail_problem
    solver, _ = _solvers(plan, fx)
    c_star = fx.dense_tail_info["c_star"]
    ptr, rows, cols, vidx, level_cols, col_ptr = solver._bwd_levels
    tail_u = (cols >= c_star)
    assert tail_u.any() and not np.any(rows >= c_star)
    assert np.all(np.flatnonzero(tail_u) < ptr[1])
    assert np.array_equal(np.sort(level_cols), np.arange(c_star))
    indptr = np.asarray(plan.indptr)
    indices = np.asarray(plan.indices)
    cols_of = np.repeat(np.arange(plan.n), np.diff(indptr))
    want = np.flatnonzero((indices < c_star) & (cols_of >= c_star))
    assert np.array_equal(np.sort(vidx[tail_u]), want)


def _corrupt_first_bwd_group(solver, plan, c_star, how):
    """The full backward schedule with the tail columns' U entries in its
    first level dropped or pointed at another entry."""
    fwd, bwd = solver._full_schedule
    g = [np.asarray(a).copy() for a in bwd[0]]
    lcols, ldiag, rows, cols, vidx = g
    hit = np.flatnonzero((cols[0] >= c_star) & (cols[0] < plan.n))
    assert len(hit)
    if how == "drop_ustep":
        rows[0, hit] = plan.n
        cols[0, hit] = plan.n
        vidx[0, hit] = plan.nnz
    else:
        vidx[0, hit[0]] = plan.diag_idx[0]
    return (tuple(g),) + tuple(bwd[1:])


@pytest.mark.parametrize("how", ["pos", "pos_shape", "drop_ustep",
                                 "ustep_vidx", "reversed"])
def test_verifier_flags_corrupted_tail(tail_problem, how):
    _, plan, fx, _ = tail_problem
    solver, _ = _solvers(plan, fx)
    assert verify_trisolver(solver).ok
    c_star = fx.dense_tail_info["c_star"]
    tail = np.asarray(solver._tail)
    if how == "pos":
        bad = tail.copy()
        bad[0, 0], bad[1, 1] = bad[1, 1], bad[0, 0]
        rep = verify_trisolver(solver, tail=bad)
        code = "TRISOLVE_DENSE_TAIL"
    elif how == "pos_shape":
        bad = np.pad(tail, ((0, 1), (0, 1)), constant_values=plan.nnz)
        rep = verify_trisolver(solver, tail=bad)
        code = "TRISOLVE_DENSE_TAIL"
    elif how == "reversed":
        # the prefix levels run before the tail's U entries reach them
        bwd = tuple(reversed(solver._full_schedule[1]))
        rep = verify_trisolver(solver, bwd_groups=bwd)
        code = "TRISOLVE_BWD_RACE"
    else:
        bwd = _corrupt_first_bwd_group(solver, plan, c_star, how)
        rep = verify_trisolver(solver, bwd_groups=bwd)
        code = "TRISOLVE_BWD_SET"
    assert code in rep.codes, rep.violations


_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_ENABLE_X64"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.core import GLU
from repro.distributed import make_sweep_mesh
from repro.sparse import circuit_jacobian

A = circuit_jacobian(300, avg_degree=4.5, seed=11)
rng = np.random.default_rng(0)
vals = np.asarray(A.data)[None] * (1.0 + 0.1 * rng.uniform(-1, 1, (4, A.nnz)))
rhs = rng.normal(size=(4, A.n))
ref = GLU(A, refine=1)
want = ref.refactorize_solve(vals, rhs)
g = GLU(A, refine=1, mesh=make_sweep_mesh(4))
got = g.refactorize_solve(vals, rhs)
info = g.solve_info
assert info["n_devices"] == 4, info
assert info["trisolve_dense_tail"] == \
    g._factorizer.dense_tail_info["size"] > 0, info
assert info["solve_dispatches"] == ref.solve_info["solve_dispatches"]
np.testing.assert_array_equal(want, got)
print("SHARDED_OK")
"""


def test_sharded_sweep_bitwise_equals_one_device():
    """The dense step runs inside ``shard_map`` like the level bodies: a
    4-device sharded sweep equals the one-device batched sweep bitwise."""
    r = subprocess.run([sys.executable, "-c", _SHARDED], capture_output=True,
                       text=True, cwd=Path(__file__).resolve().parents[1],
                       timeout=300)
    assert "SHARDED_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
