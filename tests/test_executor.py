"""Single-dispatch schedule executor: bucketed ragged fusion + whole-schedule
jit.

Contracts under test:
 * bit-identity — the bucketed-fusion + whole-schedule-jit executor returns
   BIT-identical factors/solutions to the unfused per-level reference
   (``fuse_levels=False, jit_schedule=False``) across the full mode matrix:
   flat/segmented/panel overrides, pallas, dense tail, single + batched,
   real + complex, robust (static pivot) + plain;
 * dispatch accounting — the fused path issues exactly ONE device dispatch
   per factorization / triangular solve (``last_n_dispatches``, surfaced as
   ``solve_info["n_dispatches"]`` / ``["solve_dispatches"]``);
 * executable-cache reuse — a second executor on the same plan pulls the
   SAME runner object from the process-wide cache (compiles nothing);
 * sparse-RHS full-reach shortcut — a pattern whose reach closure covers
   every column reuses the full schedule object instead of building a
   redundant pruned twin.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    GLU,
    ExecutableCache,
    JaxFactorizer,
    JaxTriangularSolver,
    build_plan,
    default_executable_cache,
    factorize_numpy,
    fill_reducing_ordering,
    symbolic_fillin_gp,
)
from repro.core.plan import MODE_FLAT, MODE_PANEL, MODE_SEGMENTED, choose_buckets
from repro.sparse import circuit_jacobian, unpack_planes


@pytest.fixture(scope="module")
def problem():
    A = circuit_jacobian(220, avg_degree=4.0, seed=7)
    As = symbolic_fillin_gp(A)
    plan = build_plan(As)
    oracle = factorize_numpy(As, As.filled_csc(A).data)
    return A, plan, oracle


@pytest.fixture(scope="module")
def dense_problem():
    A0 = circuit_jacobian(500, avg_degree=4.0, seed=22)
    perm = fill_reducing_ordering(A0, "mindeg")
    A = A0.permute(perm, perm)
    As = symbolic_fillin_gp(A)
    plan = build_plan(As)
    return A, plan


def _reference(plan, dtype, **kw):
    """The seed executor: per-level, per-group-dispatch."""
    return JaxFactorizer(plan, dtype=dtype, fuse_levels=False,
                         jit_schedule=False, **kw)


# -- bucket ladder unit behavior -------------------------------------------

def test_choose_buckets_waste_bound():
    sizes = [3, 5, 9, 17, 33, 200, 1000]
    ladder = choose_buckets(sizes, max_waste=4.0)
    assert list(ladder) == sorted(set(ladder))
    # every pow2 pad lands on a bucket within the waste bound
    from repro.core.plan import bucketize, pow2_pad
    for s in sizes:
        p = pow2_pad(s)
        b = bucketize(p, ladder)
        assert p <= b <= 4.0 * p


def test_bucketing_reduces_groups(problem):
    _, plan, _ = problem
    exact = JaxFactorizer(plan, dtype=jnp.float64, fuse_buckets=False)
    bucketed = JaxFactorizer(plan, dtype=jnp.float64)
    assert bucketed.n_groups <= exact.n_groups
    # the long narrow schedules this repo targets collapse substantially
    assert bucketed.n_groups < plan.num_levels // 4


# -- bit-identity matrix ----------------------------------------------------

CONFIGS = [
    pytest.param(dict(), id="default"),
    pytest.param(dict(mode_override=MODE_FLAT), id="flat"),
    pytest.param(dict(mode_override=MODE_SEGMENTED), id="segmented"),
    pytest.param(dict(mode_override=MODE_PANEL), id="panel"),
    pytest.param(dict(use_pallas=True), id="pallas"),
    pytest.param(dict(static_pivot=1e-10), id="robust"),
    pytest.param(dict(use_pallas=True, static_pivot=1e-10),
                 id="pallas-robust"),
    pytest.param(dict(fuse_buckets=False), id="nobuckets"),
]


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.complex128],
                         ids=["real", "complex"])
@pytest.mark.parametrize("kw", CONFIGS)
def test_fused_bit_identical_single(problem, kw, dtype):
    A, plan, _ = problem
    a = np.asarray(A.data, dtype=np.dtype(dtype))
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = a + 1j * np.linspace(-1, 1, len(a))
    ref = _reference(plan, dtype, **kw)
    out_ref = np.asarray(ref.factorize(a))
    fx = JaxFactorizer(plan, dtype=dtype, **kw)
    out = np.asarray(fx.factorize(a))
    assert out.tobytes() == out_ref.tobytes()
    assert fx.last_n_dispatches == 1
    assert ref.last_n_dispatches > 10 * fx.last_n_dispatches


@pytest.mark.parametrize("kw", CONFIGS)
def test_fused_bit_identical_batched(problem, kw):
    A, plan, _ = problem
    rng = np.random.default_rng(3)
    batch = rng.standard_normal((3, A.nnz))
    ref = _reference(plan, jnp.float64, **kw)
    out_ref = np.stack([np.asarray(ref.factorize(v)) for v in batch])
    fx = JaxFactorizer(plan, dtype=jnp.float64, **kw)
    out = np.asarray(fx.factorize_batched(batch))
    assert out.tobytes() == out_ref.tobytes()
    assert fx.last_n_dispatches == 1


def test_fused_bit_identical_dense_tail(dense_problem):
    A, plan = dense_problem
    a = np.asarray(A.data)
    for kw in (dict(dense_tail=True), dict(dense_tail=True, use_pallas=True),
               dict(dense_tail=True, static_pivot=1e-10)):
        ref = _reference(plan, jnp.float64, **kw)
        if ref.dense_tail_info is None:
            pytest.skip("no dense tail found for this instance")
        fx = JaxFactorizer(plan, dtype=jnp.float64, **kw)
        assert np.asarray(fx.factorize(a)).tobytes() == \
            np.asarray(ref.factorize(a)).tobytes()
        # batched twin (always XLA dense LU on both paths)
        batch = np.stack([a, a * 0.5])
        out_b = np.asarray(fx.factorize_batched(batch))
        ref_b = np.stack([np.asarray(ref.factorize(v)) for v in batch])
        assert out_b.tobytes() == ref_b.tobytes()


def test_fused_filled_entry_matches(problem):
    """factorize_filled (pre-scattered values, donated) == factorize."""
    A, plan, _ = problem
    fx = JaxFactorizer(plan, dtype=jnp.float64)
    out = np.asarray(fx.factorize(A.data))
    vals = jnp.zeros(plan.nnz, dtype=jnp.float64
                     ).at[jnp.asarray(plan.a_scatter)].set(
                         jnp.asarray(A.data, dtype=jnp.float64))
    out2 = np.asarray(fx.factorize_filled(vals))
    assert out.tobytes() == out2.tobytes()


def test_robust_diagnostics_match_legacy(problem):
    A, plan, _ = problem
    a = np.asarray(A.data).copy()
    a[0] = 1e-18                            # force a perturbation somewhere
    ref = _reference(plan, jnp.float64, static_pivot=1e-8)
    fx = JaxFactorizer(plan, dtype=jnp.float64, static_pivot=1e-8)
    out_ref = np.asarray(ref.factorize(a))
    out = np.asarray(fx.factorize(a))
    assert out.tobytes() == out_ref.tobytes()
    assert float(fx.last_a_max) == float(ref.last_a_max)
    assert int(fx.last_n_perturbed) == int(ref.last_n_perturbed)


# -- triangular solver ------------------------------------------------------

def test_trisolve_fused_bit_identical(problem):
    A, plan, _ = problem
    fx = JaxFactorizer(plan, dtype=jnp.float64)
    vals = fx.factorize(A.data)
    rng = np.random.default_rng(5)
    b = rng.standard_normal(plan.n)
    legacy = JaxTriangularSolver(plan, fuse_buckets=False, jit_schedule=False)
    fused = JaxTriangularSolver(plan)
    xl = np.asarray(legacy.solve(vals, b))
    xf = np.asarray(fused.solve(vals, b))
    assert xf.tobytes() == xl.tobytes()
    assert fused.last_n_dispatches == 1
    assert legacy.last_n_dispatches > 10
    # batched + multi twins
    vb = jnp.stack([vals, vals * 0.5])
    bb = rng.standard_normal((2, plan.n))
    assert np.asarray(fused.solve_batched(vb, bb)).tobytes() == \
        np.asarray(legacy.solve_batched(vb, bb)).tobytes()
    bm = rng.standard_normal((4, plan.n))
    assert np.asarray(fused.solve_multi(vals, bm)).tobytes() == \
        np.asarray(legacy.solve_multi(vals, bm)).tobytes()


def test_trisolve_fused_does_not_clobber_rhs(problem):
    """The fused runner must not donate the caller's rhs or factor values."""
    A, plan, _ = problem
    fx = JaxFactorizer(plan, dtype=jnp.float64)
    vals = fx.factorize(A.data)
    solver = JaxTriangularSolver(plan)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(plan.n))
    x1 = np.asarray(solver.solve(vals, b))
    x2 = np.asarray(solver.solve(vals, b))      # b and vals still alive
    assert x1.tobytes() == x2.tobytes()


def test_trisolve_sparse_pruned_bit_identical(problem):
    A, plan, _ = problem
    fx = JaxFactorizer(plan, dtype=jnp.float64)
    vals = fx.factorize(A.data)
    pat = [2, 11]
    b = np.zeros(plan.n)
    b[pat] = 1.0
    legacy = JaxTriangularSolver(plan, fuse_buckets=False, jit_schedule=False)
    fused = JaxTriangularSolver(plan)
    _, _, _, breach = fused.schedule_for_pattern(pat)
    xl = np.asarray(legacy.solve(vals, b, rhs_pattern=pat))
    xf = np.asarray(fused.solve(vals, b, rhs_pattern=pat))
    assert xf.tobytes() == xl.tobytes()
    full = np.asarray(fused.solve(vals, b))
    np.testing.assert_array_equal(xf[breach], full[breach])


def test_full_reach_pattern_reuses_full_schedule(problem):
    """Satellite: a pattern whose closure is every column must NOT build a
    pruned twin of the full schedule."""
    _, plan, _ = problem
    solver = JaxTriangularSolver(plan)
    dense_pat = np.arange(plan.n)
    fwd, bwd, freach, breach = solver.schedule_for_pattern(dense_pat)
    assert len(freach) == plan.n and len(breach) == plan.n
    assert fwd is solver._full_schedule[0]
    assert bwd is solver._full_schedule[1]
    # and the executable-cache key resolves to the full schedule's runner
    assert solver._groups_for(dense_pat)[2] == "full"


# -- executable cache -------------------------------------------------------

def test_executable_cache_shared_across_instances(problem):
    """Second executor on the same plan compiles nothing: it gets the SAME
    runner callable back from the process-wide cache."""
    A, plan, _ = problem
    fx1 = JaxFactorizer(plan, dtype=jnp.float64)
    fx1.factorize(A.data)
    r1 = fx1._runner_for("scatter", False)
    cache = default_executable_cache()
    hits0 = cache.stats.hits
    builds0 = cache.stats.builds
    fx2 = JaxFactorizer(plan, dtype=jnp.float64)
    out = np.asarray(fx2.factorize(A.data))
    r2 = fx2._runner_for("scatter", False)
    assert r1 is r2
    assert cache.stats.hits > hits0
    assert cache.stats.builds == builds0        # nothing new was built
    assert out.tobytes() == np.asarray(fx1.factorize(A.data)).tobytes()


def test_private_executable_cache_isolated(problem):
    A, plan, _ = problem
    default_stats0 = default_executable_cache().stats.snapshot()
    private = ExecutableCache(capacity=4)
    fx = JaxFactorizer(plan, dtype=jnp.float64, executable_cache=private)
    fx.factorize(A.data)
    assert len(private) == 1
    assert private.stats.builds == 1
    assert fx._runner_key("scatter", False) in private
    # the process-wide cache was never consulted
    assert default_executable_cache().stats.snapshot() == default_stats0


def test_executable_cache_layout_keys_disjoint(problem):
    """Planar and native runners on the SAME plan+dtype must not collide in
    the executable cache — the layout is part of every runner key."""
    A, plan, _ = problem
    a = np.asarray(A.data, dtype=np.complex128) * (1 + 0.5j)
    cache = ExecutableCache(capacity=16)
    nat = JaxFactorizer(plan, dtype=jnp.complex128, executable_cache=cache)
    pla = JaxFactorizer(plan, dtype=jnp.complex128, layout="planar",
                        executable_cache=cache)
    kn, kp = nat._runner_key("scatter", False), pla._runner_key("scatter", False)
    assert kn != kp
    assert kn[-1] == "native" and kp[-1] == "planar"
    out_n = np.asarray(nat.factorize(a))
    builds_nat = cache.stats.builds
    out_p = np.asarray(unpack_planes(pla.factorize(a)))
    # planar built its own runner — a key collision would have silently
    # handed the native runner planar-shaped inputs
    assert cache.stats.builds > builds_nat
    np.testing.assert_allclose(out_p, out_n, rtol=1e-12, atol=1e-14)
    # trisolve keys carry the layout the same way
    sn = JaxTriangularSolver(plan, executable_cache=cache)
    sp_ = JaxTriangularSolver(plan, layout="planar", executable_cache=cache)
    b = np.random.default_rng(9).standard_normal(plan.n).astype(np.complex128)
    xn = np.asarray(sn.solve(nat.factorize(a), b))
    xp = np.asarray(sp_.solve(pla.factorize(a), b))
    np.testing.assert_allclose(xp, xn, rtol=1e-12, atol=1e-14)


def test_executable_cache_hit_on_repeated_planar(problem):
    """A second planar factorizer on the same plan compiles nothing."""
    A, plan, _ = problem
    a = np.asarray(A.data, dtype=np.complex128) * (1 - 0.25j)
    cache = ExecutableCache(capacity=16)
    fx1 = JaxFactorizer(plan, dtype=jnp.complex128, layout="planar",
                        executable_cache=cache)
    out1 = np.asarray(fx1.factorize(a))
    builds0, hits0 = cache.stats.builds, cache.stats.hits
    fx2 = JaxFactorizer(plan, dtype=jnp.complex128, layout="planar",
                        executable_cache=cache)
    out2 = np.asarray(fx2.factorize(a))
    assert cache.stats.builds == builds0        # nothing new was built
    assert cache.stats.hits > hits0
    assert fx1._runner_for("scatter", False) is fx2._runner_for("scatter", False)
    assert out1.tobytes() == out2.tobytes()


def test_executable_cache_lru_eviction():
    c = ExecutableCache(capacity=2)
    c.get_or_build("a", lambda: "A")
    c.get_or_build("b", lambda: "B")
    c.get_or_build("a", lambda: "A2")           # hit refreshes recency
    c.get_or_build("c", lambda: "C")            # evicts "b"
    assert "b" not in c and "a" in c and "c" in c
    assert c.stats.evictions == 1


# -- facade wiring ----------------------------------------------------------

def test_glu_solve_info_dispatch_counters(problem):
    A, _, _ = problem
    glu = GLU(A, dtype=jnp.float64).factorize()
    b = np.random.default_rng(2).standard_normal(A.n)
    glu.solve(b)
    info = glu.solve_info
    assert info["n_dispatches"] == 1
    assert info["solve_dispatches"] == 1
    assert info["n_groups"] >= 1
    legacy = GLU(A, dtype=jnp.float64, fuse_levels=False,
                 jit_schedule=False).factorize()
    legacy.solve(b)
    li = legacy.solve_info
    assert li["n_dispatches"] >= 10 * info["n_dispatches"]
    assert li["solve_dispatches"] >= 10 * info["solve_dispatches"]


# Programs a refined solve launches on the fused path: the first trisolve
# and residual, the iteration count's zeros (convert_element_type, plus
# broadcast_in_dim for a vector), then per sweep trisolve, correction,
# compare, count add and residual; |A| once per factorization.
@pytest.mark.parametrize("kind, zeros", [("single", 1), ("batched", 2),
                                         ("multi", 2)])
def test_glu_refined_solve_dispatch_count(problem, kind, zeros):
    """``solve_dispatches`` counts every device program of a refined solve
    (refine=3 with a tolerance no sweep meets: three sweeps, two syncs)."""
    A, _, _ = problem
    rng = np.random.default_rng(5)
    glu = GLU(A, dtype=jnp.float64, refine_tol=0.0)
    if kind == "batched":
        glu.factorize_batched(np.stack([A.data, 1.01 * A.data]))
    else:
        glu.factorize()
    solve = {"single": lambda: glu.solve(rng.standard_normal(A.n), refine=3),
             "batched": lambda: glu.solve_batched(
                 rng.standard_normal((2, A.n)), refine=3),
             "multi": lambda: glu.solve_multi(
                 rng.standard_normal((2, A.n)), refine=3)}[kind]
    per_solve = 1 + 1 + zeros + 3 * 5
    solve()
    info = glu.solve_info
    assert np.all(info["refine_iters"] == 3) and info["host_syncs"] == 2
    assert info["n_dispatches"] == 1
    assert info["solve_dispatches"] == per_solve + 1
    solve()                                 # |A| is already on the device
    assert glu.solve_info["solve_dispatches"] == per_solve


def test_glu_fused_matches_legacy_end_to_end(problem):
    A, _, _ = problem
    b = np.random.default_rng(4).standard_normal(A.n)
    x_fused = GLU(A, dtype=jnp.float64).factorize().solve(b)
    x_legacy = GLU(A, dtype=jnp.float64, fuse_levels=False,
                   jit_schedule=False).factorize().solve(b)
    assert np.asarray(x_fused).tobytes() == np.asarray(x_legacy).tobytes()
