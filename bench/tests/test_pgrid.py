"""The power-grid configuration: its frozen generator still gives the
program's ``grid128``, its bytes are pinned, its re-stamping keeps each
branch's stamp and every row's leak, its cell is correct at a CPU size and
its float32 control is not, and its readers read nothing without the
program's counters."""
import hashlib
import time

import numpy as np
import pytest
import scipy.sparse as sp

import gen
import run

SPEC = run.load_spec()
CELL = "pgrid16384.newton"
CONFIG = run.load_config(SPEC, "pgrid16384")
# SHA-256 of the arrays' bytes at the configuration's args (pattern seed 0)
# and of a value pool drawn with seed 3
DIGESTS = {
    "indptr": "17fdeb2fbe3898663ea1687ab42cefc719cbbef337091c1e1251b01243114942",
    "indices": "17a2106a1c95b3e75f657ec5f144b3f7389c98ecc8d82ed8190f278ead2bb775",
    "data": "20f555cdda297ff363ef84d216607505a38c8d02f6e768883e3eb267d8fe614a",
    "value_pool": "31329ea6524ff534f0f913b706cc71e2e70513b563706a08fb263e69820514a8",
}
READERS = ["factorize_update_entries.newton", "factorize_levels.newton"]


def test_rc_mesh_reproduces_the_program_grid128():
    from repro.sparse import make_suite_matrix

    A = make_suite_matrix("grid128", scale=1.0, seed=0)
    n, (indptr, indices, data), _ = gen.make_matrix(CONFIG)
    assert n == A.n == 128 * 128
    assert np.array_equal(indptr, A.indptr)
    assert np.array_equal(indices, A.indices)
    assert np.array_equal(data, np.asarray(A.data))


def test_rc_mesh_gives_the_pinned_bytes():
    n, (indptr, indices, data), net = gen.make_matrix(CONFIG)
    pool = gen.value_pool(net, np.random.default_rng(3), 2, 0.1)
    got = {k: hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
           for k, a in (("indptr", indptr), ("indices", indices),
                        ("data", data), ("value_pool", pool))}
    assert got == DIGESTS


def test_restamping_moves_one_branch_and_keeps_every_leak():
    n, (indptr, indices, data), net = gen.make_matrix(CONFIG)
    assert net.n_branches == 2 * 128 * 127
    base = net.restamp(np.ones(net.n_branches))
    np.testing.assert_allclose(base, data, rtol=1e-14, atol=1e-14)
    f = np.ones(net.n_branches)
    f[net.n_branches // 3] = 1.1
    assert len(np.flatnonzero(net.restamp(f) != base)) == 4
    v = gen.value_pool(net, np.random.default_rng(3), 2, 0.1)
    S = sp.csc_matrix((v[1], indices, indptr), shape=(n, n))
    off = S - sp.diags(S.diagonal())
    leak = S.diagonal() - np.asarray(abs(off).sum(axis=1)).ravel()
    np.testing.assert_allclose(leak, 1e-3, rtol=1e-9)


def test_the_generator_refuses_a_size_that_is_not_the_mesh():
    with pytest.raises(ValueError, match="nx"):
        gen.make_matrix({**CONFIG, "args": {"n": 100, "nx": 8, "ny": 8}})


@pytest.mark.parametrize("value_dtype,correct", [(None, True),
                                                 ("float32", False)])
def test_the_cell_at_a_cpu_size(value_dtype, correct):
    """An 80x80 mesh (n = 6400, above 6000, so ordered by AMD as the cell
    is) through ``run_cell``: sound in the configuration's float64, and not
    correct in the float32 control."""
    small = {**CONFIG, "args": {"n": 6400, "nx": 80, "ny": 80, "seed": 0}}
    r = run.run_cell(CELL, 2**31 + 41, 0.5, False, config=small,
                     value_dtype=value_dtype, require_tpu=False,
                     t0=time.perf_counter())
    assert r["correct"] is correct, r["checks"]
    assert set(r["metrics"]) == {"setup_s", "newton_step_ms",
                                 "newton_step_p95_ms"}
    if not correct:
        assert r["checks"]["berr"]["value"] > r["checks"]["berr"]["limit"]
        assert r["checks"]["fwd_err"]["value"] > r["checks"]["fwd_err"]["limit"]


@pytest.mark.parametrize("name", READERS)
def test_the_new_readers_read_nothing_without_the_counters(name):
    read = run.load_reader(name)
    assert read({}) is None
    ctx = {"kind": "newton", "solve_info": [{"refine_iters": 3}]}
    assert read(ctx) is None
    assert read({**ctx, "solve_info": [{name.split(".")[0]: 7}]}) == 7
