"""Approximate minimum degree (``amd``): a permutation, fill within 10% of
exact minimum degree, what ``"auto"`` runs above 6000 columns, and a
default ``GLU`` on a mesh that solves to full accuracy in few levels; and
the factorize counters ``solve_info`` carries."""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.linalg import splu

from repro.core import GLU, amd, minimum_degree
from repro.core.ordering import fill_reducing_ordering, resolve_ordering_method
from repro.core.planner import plan_key
from repro.core.symbolic import symbolic_fillin
from repro.sparse import grid_laplacian, make_suite_matrix, rc_ladder


def _fill(A, perm):
    return symbolic_fillin(A.permute(perm, perm), "auto").nnz


MATRICES = {
    "mesh20": lambda: grid_laplacian(20, 20),
    "mesh30": lambda: grid_laplacian(30, 30),
    "mesh40": lambda: grid_laplacian(40, 40),
    "mesh50": lambda: grid_laplacian(50, 50),
    "mesh60": lambda: grid_laplacian(60, 60),
    "rajat12_like": lambda: make_suite_matrix("rajat12_like", scale=0.3),
    "memplus_like": lambda: make_suite_matrix("memplus_like", scale=0.1),
}


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_amd_returns_a_permutation(name):
    A = MATRICES[name]()
    perm = amd(A)
    assert perm.dtype == np.int64
    assert np.array_equal(np.sort(perm), np.arange(A.n))
    assert np.array_equal(amd(A), perm)          # deterministic


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_amd_fill_is_within_a_tenth_of_exact_minimum_degree(name):
    A = MATRICES[name]()
    assert _fill(A, amd(A)) <= 1.1 * _fill(A, minimum_degree(A))


def test_amd_leaves_the_ladder_without_fill():
    A = rc_ladder(2000)
    assert _fill(A, amd(A)) == A.nnz


def _shapes(g):
    """The factorizer's groups and the trisolve's full schedule, as
    shapes: what the device programs walk."""
    groups = [(grp.kind, grp.mode, grp.n_levels,
               tuple(a.shape for a in grp.arrays))
              for grp in g._factorizer._groups]
    fwd, bwd = g._solver._full_schedule
    sched = [tuple(a.shape for a in grp) for grp in (*fwd, *bwd)]
    return groups, sched


def test_auto_plans_the_full_ladder_as_rcm_did():
    """The 17,758-node ladder is above 6000 columns, so ``auto`` now runs
    AMD where it ran RCM: the plan is still one chain of 17,758 levels and
    the device programs walk groups of the same shapes."""
    A = rc_ladder(17758)
    g = GLU(A, dtype=jnp.float64, plan_cache=None)
    assert g.symbolic_plan.ordering == "amd"
    assert g.num_levels == A.n
    assert g.symbolic_plan.fplan.nnz == A.nnz
    rcm = GLU(A, dtype=jnp.float64, plan_cache=None, ordering="rcm")
    assert _shapes(g) == _shapes(rcm)


@pytest.mark.parametrize("n,method", [(6000, "mindeg"), (6001, "amd")])
def test_auto_resolves_by_size(n, method):
    assert resolve_ordering_method(n, "auto") == method


@pytest.mark.parametrize("method", ["none", "mindeg", "amd", "rcm"])
def test_named_methods_resolve_to_themselves(method):
    assert resolve_ordering_method(7000, method) == method
    with pytest.raises(ValueError):
        resolve_ordering_method(7000, "colamd")


def test_auto_and_amd_share_a_plan_key_above_6000():
    A = grid_laplacian(80, 80)
    rp = np.arange(A.n)
    key = plan_key(A.n, A.indptr, A.indices, rp, "auto", "auto", 16)
    assert key == plan_key(A.n, A.indptr, A.indices, rp, "amd", "auto", 16)
    assert key != plan_key(A.n, A.indptr, A.indices, rp, "rcm", "auto", 16)
    assert np.array_equal(fill_reducing_ordering(A, "auto"), amd(A))


def _berr(S, x, b):
    return float(np.max(np.abs(b - S @ x) / (abs(S) @ np.abs(x) + np.abs(b))))


def test_default_glu_on_a_mesh_orders_by_amd_and_solves():
    """An 80x80 mesh (n = 6400) through ``GLU(A)`` with defaults: AMD
    ordering, fewer than n/4 levels, backward error at most 1e-12 and
    scipy's answer to 1e-9."""
    A = grid_laplacian(80, 80)
    g = GLU(A, dtype=jnp.float64, plan_cache=None)
    assert g.symbolic_plan.ordering == "amd"
    assert g.num_levels < A.n // 4
    S = sp.csc_matrix((np.asarray(A.data), A.indices, A.indptr),
                      shape=(A.n, A.n))
    b = np.random.default_rng(5).standard_normal(A.n)
    g.factorize()
    x = g.solve(b, refine=3)
    assert _berr(S, x, b) <= 1e-12
    xs = splu(S).solve(b)
    assert np.max(np.abs(x - xs)) <= 1e-9 * np.max(np.abs(xs))


@pytest.mark.parametrize("name,tail", [("rajat12_like", True),
                                       ("ladder", False)])
def test_factorize_counters_equal_the_factorizer_groups(name, tail):
    A = (make_suite_matrix("rajat12_like", scale=0.3) if name != "ladder"
         else rc_ladder(300))
    g = GLU(A, dtype=jnp.float64, plan_cache=None)
    g.factorize()
    info = g.solve_info
    f = g._factorizer
    sparse = [grp for grp in f._groups if grp.kind != "dense"]
    assert info["factorize_levels"] == sum(grp.n_levels for grp in sparse)
    assert info["factorize_update_entries"] == sum(
        int(np.prod(grp.arrays[2].shape)) for grp in sparse)
    plan = g.symbolic_plan.fplan
    cut = (f.dense_tail_info["level_cut"] if tail else plan.num_levels)
    assert info["factorize_levels"] == cut
    assert info["factorize_update_entries"] >= sum(
        s.n_upd for s in plan.segments if s.level < cut)
    if tail:
        assert info["factorize_dense_tail"] == f.dense_tail_info["size"] > 0
    else:
        assert f.dense_tail_info is None
        assert info["factorize_dense_tail"] == 0
    g.solve(np.ones(A.n))
    assert g.solve_info["factorize_levels"] == info["factorize_levels"]
