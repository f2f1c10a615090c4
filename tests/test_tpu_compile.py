"""Ahead-of-time compiles for a described TPU v5e, plus the platform rules.

The TPU compiler ships with JAX and compiles for a chip that is described
rather than attached.  These compiles refuse what interpret mode accepts:
block shapes that break the (8, 128) tiling rule, kernels Mosaic cannot
lower, scoped-VMEM overruns.  Nothing runs, so they say nothing about
results or times.

The topology is described only inside the module fixture below, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file.  complex128 is never compiled for the
described chip — the TPU compiler aborts the whole process on c128.  The
platform-rule tests at the bottom steer the backend query instead.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import GLU, JaxFactorizer, resolve_value_dtype
from repro.core.triangular import _build_trisolve_runner
from repro.kernels import dense_lu, dense_lu_planar, segmented_accumulate
from repro.kernels.backend import resolve_interpret
from repro.sparse import make_suite_matrix


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    """ShapeDtypeStructs on the described device for every array leaf."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (D, R, C) of SEGMENTED/PANEL levels the zoo produces: rajat12_like's
# widest level at full size, a grid64 level, a memplus_like panel level
@pytest.mark.parametrize("D,R,C", [(801, 512, 1024), (302, 256, 256),
                                   (1, 256, 128)])
def test_segmented_accumulate_compiles(one_chip, D, R, C):
    args = (jax.ShapeDtypeStruct((D, C), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((D, R), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((D, R), jnp.int32, sharding=one_chip))
    c = _compile(lambda a, b, d: segmented_accumulate(a, b, d,
                                                      interpret=False), *args)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel,shape", [
    pytest.param(dense_lu, (768, 768), id="dense_lu-768"),
    pytest.param(dense_lu_planar, (2, 256, 256), id="dense_lu_planar-256"),
])
def test_dense_lu_compiles(one_chip, kernel, shape):
    # 768 is rajat12_like's full-size dense tail (726 padded); it needs
    # more than the default 16 MiB of scoped VMEM, which the kernel asks for
    a = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    c = _compile(lambda x: kernel(x, interpret=False), a)
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes == mem.output_size_in_bytes == \
        int(np.prod(shape)) * 4


@pytest.fixture(scope="module")
def zoo_matrix():
    return make_suite_matrix("rajat12_like", scale=0.3)


def test_f64_factorize_and_trisolve_runners_compile(one_chip, zoo_matrix):
    """The default path: float64 whole-schedule programs, XLA only."""
    A = zoo_matrix
    g = GLU(A)
    fx = g._factorizer
    a = jax.ShapeDtypeStruct((A.nnz,), jnp.float64, sharding=one_chip)
    c = fx._runner_for("scatter", False).lower(
        a, *_shapes((fx._a_scatter, fx._group_arrays, fx._group_diags),
                    one_chip), None).compile()
    assert "tpu_custom_call" not in c.as_text()
    fwd, bwd = g._solver._full_schedule
    vals = jax.ShapeDtypeStruct((g.nnz_filled,), jnp.float64,
                                sharding=one_chip)
    b = jax.ShapeDtypeStruct((A.n,), jnp.float64, sharding=one_chip)
    _build_trisolve_runner("single").lower(
        vals, b, *_shapes((tuple(fwd), tuple(bwd)), one_chip)).compile()


@pytest.mark.parametrize("kind", ["single", "batched"])
def test_f64_dense_tail_trisolve_runner_compiles(one_chip, zoo_matrix, kind):
    """The dense-tail trisolve: prefix levels, the block gathered from
    ``vals`` and dense substitution on it, then the prefix backward levels,
    as one float64 program."""
    A = zoo_matrix
    g = GLU(A)
    info = g._factorizer.dense_tail_info
    assert info is not None
    solver = g._solver
    fwd, bwd = solver._full_schedule
    batch = (4,) if kind == "batched" else ()
    vals = jax.ShapeDtypeStruct(batch + (g.nnz_filled,), jnp.float64,
                                sharding=one_chip)
    b = jax.ShapeDtypeStruct(batch + (A.n,), jnp.float64, sharding=one_chip)
    c = _build_trisolve_runner(kind).lower(
        vals, b, *_shapes((tuple(fwd), tuple(bwd), solver._tail), one_chip)
    ).compile()
    assert "tpu_custom_call" not in c.as_text()


def test_f32_pallas_runner_compiles(one_chip, zoo_matrix):
    """The paper's kernel path: SEGMENTED levels and the dense tail as
    Mosaic kernels inside the one fused factorize program."""
    A = zoo_matrix
    g = GLU(A, dtype=jnp.float32, use_pallas=True, interpret=False)
    fx = g._factorizer
    kinds = set(fx._kinds)
    assert {"pallas", "dense"} <= kinds, kinds
    a = jax.ShapeDtypeStruct((A.nnz,), jnp.float32, sharding=one_chip)
    c = fx._runner_for("scatter", False).lower(
        a, *_shapes((fx._a_scatter, fx._group_arrays, fx._group_diags),
                    one_chip), None).compile()
    assert "tpu_custom_call" in c.as_text()


# -- platform rules, with the backend query steered to "tpu" ---------------

@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def small_matrix():
    return make_suite_matrix("rajat12_like", scale=0.1)


def test_complex128_refused_on_tpu(as_tpu, small_matrix):
    with pytest.raises(ValueError, match="complex64"):
        resolve_value_dtype(jnp.complex128)
    with pytest.raises(ValueError, match="complex64"):
        GLU(small_matrix, dtype=jnp.complex128)
    assert resolve_value_dtype(jnp.complex64) == np.dtype(np.complex64)


def test_ac_sweep_refuses_complex128_on_tpu(as_tpu):
    from repro.circuit import ac_sweep, rc_grid_circuit

    ckt = rc_grid_circuit(3, 3, with_diodes=False, seed=0)
    ckt.add_ac_current_source(1, 0, 1.0)
    with pytest.raises(ValueError, match="complex64"):
        ac_sweep(ckt, np.logspace(0, 3, 4))


def test_pallas_f64_refused_on_tpu(as_tpu, small_matrix):
    with pytest.raises(ValueError, match="float32"):
        GLU(small_matrix, use_pallas=True)
    # the XLA path keeps its (emulated) float64 default
    assert GLU(small_matrix).dtype == np.dtype(np.float64)


def test_interpret_resolves_from_platform(small_matrix, monkeypatch):
    g = GLU(small_matrix, dtype=jnp.float32, use_pallas=True)
    assert g._factorizer.interpret          # not a TPU: interpret mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fx = JaxFactorizer(g.plan, dtype=jnp.float32, use_pallas=True)
    assert fx.interpret is False and fx.pallas_disabled_reason is None
    with pytest.raises(ValueError, match="interpret=True"):
        GLU(small_matrix, dtype=jnp.float32, use_pallas=True, interpret=True)
    with pytest.raises(ValueError, match="interpret=True"):
        resolve_interpret(True)
    assert resolve_interpret(None) is False
