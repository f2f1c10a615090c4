"""Mesh-sharded batched sweeps: scenario-sharding descriptor unit tests in
the 1-device main process, plus an 8-emulated-device subprocess proving the
sharded refactorize_solve is bit-identical to the single-device batched
path across the mode matrix (native f64, robust, sparse-only schedule,
native complex, planar complex) and that non-divisible batches pad/mask
correctly."""
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.distributed import make_scenario_sharding, make_sweep_mesh


def test_no_mesh_means_no_sharding():
    assert make_scenario_sharding(None) is None


def test_single_device_mesh_stays_unsharded():
    # a 1-device mesh resolves the scenario rule to shards of size 1, which
    # buys nothing — the factory declines rather than wrapping in shard_map
    assert make_scenario_sharding(make_sweep_mesh(1)) is None


def test_make_sweep_mesh_rejects_oversubscription():
    with pytest.raises(ValueError):
        make_sweep_mesh(jax.device_count() + 1)


def test_glu_with_single_device_mesh_is_noop():
    import jax.numpy as jnp

    from repro.core import GLU
    from repro.sparse import circuit_jacobian

    A = circuit_jacobian(60, avg_degree=4.0, seed=3)
    rng = np.random.default_rng(0)
    vals = np.asarray(A.data)[None] * (
        1.0 + 0.1 * rng.uniform(-1, 1, size=(3, A.nnz)))
    rhs = rng.normal(size=(3, A.n))
    ref = GLU(A, dtype=jnp.float64).refactorize_solve(vals, rhs)
    glu = GLU(A, dtype=jnp.float64, mesh=make_sweep_mesh(1))
    assert glu.n_devices == 1
    got = glu.refactorize_solve(vals, rhs)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert glu.solve_info["n_devices"] == 1
    assert glu.solve_info["batch_spec"] is None


def test_rhs_batch_mismatch_raises():
    import jax.numpy as jnp

    from repro.core import GLU
    from repro.sparse import circuit_jacobian

    A = circuit_jacobian(60, avg_degree=4.0, seed=3)
    glu = GLU(A, dtype=jnp.float64)
    vals = np.repeat(np.asarray(A.data)[None], 3, axis=0)
    glu.factorize_batched(vals)
    with pytest.raises(ValueError, match="does not match"):
        glu.solve_batched(np.zeros((2, A.n)))


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_ENABLE_X64"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import GLU
from repro.distributed import make_scenario_sharding, make_sweep_mesh, psum_exact
from repro.sparse import circuit_jacobian

assert jax.device_count() == 8

A = circuit_jacobian(120, avg_degree=4.0, seed=7)
rng = np.random.default_rng(0)
B = 16
vals = np.asarray(A.data)[None] * (
    1.0 + 0.1 * rng.uniform(-1, 1, size=(B, A.nnz)))
rhs = rng.normal(size=(B, A.n))
cvals = vals * np.exp(1j * rng.uniform(-0.3, 0.3, size=vals.shape))
crhs = rhs + 1j * rng.normal(size=rhs.shape)

mesh8 = make_sweep_mesh(8)
mesh4 = make_sweep_mesh(4)

# scenario-sharding descriptor math on a real multi-device mesh
s4 = make_scenario_sharding(mesh4)
assert s4 is not None and s4.n_shards == 4
assert s4.pad(7) == 8 and s4.pad(8) == 8 and s4.pad(1) == 4
s8 = make_scenario_sharding(mesh8)
assert s8.n_shards == 8 and s8.descriptor != s4.descriptor

# psum_exact really reduces across all 8 shards, exactly
tot = jax.shard_map(lambda v: psum_exact(jnp.sum(v), "data"), mesh=mesh8,
                    in_specs=(P("data"),), out_specs=P(), check_vma=False)(
                        jnp.arange(8, dtype=jnp.int64))
assert int(tot) == 28, int(tot)

# mode matrix: sharded == single-device batched, bit for bit
CONFIGS = [
    ("f64_native", dict(dtype=jnp.float64), vals, rhs),
    ("f64_robust", dict(dtype=jnp.float64, static_pivot=1e-12, refine=2),
     vals, rhs),
    ("f64_sparse_only", dict(dtype=jnp.float64, dense_tail=False), vals, rhs),
    ("c128_native", dict(dtype=jnp.complex128), cvals, crhs),
    ("c128_planar", dict(dtype=jnp.complex128, layout="planar"),
     cvals, crhs),
]
for name, kw, v, b in CONFIGS:
    g_ref = GLU(A, **kw)
    ref = g_ref.refactorize_solve(v, b)
    ref_info = g_ref.solve_info
    g = GLU(A, mesh=mesh8, **kw)
    got = g.refactorize_solve(v, b)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got),
                                  err_msg=name)
    info = g.solve_info
    assert info["n_devices"] == 8, (name, info)
    assert info["batch_spec"] == "PartitionSpec('data',)", (name, info)
    # sharding must not change the dispatch shape: one fused factorization
    # dispatch, and exactly as many solve dispatches as the single-device
    # path (refinement legitimately adds trisolve dispatches on both)
    assert info["n_dispatches"] == ref_info["n_dispatches"] == 1, (name, info)
    assert info["solve_dispatches"] == ref_info["solve_dispatches"], (
        name, info["solve_dispatches"], ref_info["solve_dispatches"])
    if "refine" not in kw:
        assert info["solve_dispatches"] == 1, (name, info)
    if "static_pivot" in kw:
        assert info["n_perturbed_global"] is not None
        assert int(info["n_perturbed_global"]) >= 0
        assert np.asarray(info["n_perturbed"]).shape == (B,)
    print("ok", name)

# padding: B=7 on a 4-device mesh pads to 8 and masks the pad row out of
# results and every per-matrix diagnostic
kw = dict(dtype=jnp.float64, static_pivot=1e-12, refine=2)
v7, b7 = vals[:7], rhs[:7]
ref = GLU(A, **kw).refactorize_solve(v7, b7)
g = GLU(A, mesh=mesh4, **kw)
got = g.refactorize_solve(v7, b7)
np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
assert got.shape == (7, A.n)
assert g.factorized_values_batched().shape[0] == 7
info = g.solve_info
assert info["n_devices"] == 4, info
for key in ("pivot_growth", "min_diag", "n_perturbed", "refine_iters"):
    assert np.asarray(info[key]).shape == (7,), (key, info[key])
print("ok padding_b7_d4")
print("SUBPROCESS_OK")
"""


def test_eight_device_sharded_sweep_integration():
    r = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                       text=True, cwd=Path(__file__).resolve().parents[1],
                       timeout=570)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
