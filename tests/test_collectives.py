"""distributed.collectives: int8 quantisation round-trip bounds and the
compressed/exact psum helpers (single-device mesh in-process; the real
8-shard reduction is exercised by test_sharded_sweep's subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import (
    compressed_psum,
    dequantize_int8,
    psum_exact,
    quantize_int8,
)


def test_int8_round_trip_error_bounded_by_half_step():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=256).astype(np.float32))
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    back = dequantize_int8(q, scale)
    # quantisation error is at most half a quantisation step
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 0.5 + 1e-6


def test_int8_round_trip_exact_on_grid_values():
    # values already on the int8 grid survive the round trip exactly
    x = jnp.asarray([-127.0, -1.0, 0.0, 1.0, 64.0, 127.0], jnp.float32)
    q, scale = quantize_int8(x)
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, scale)),
                               np.asarray(x), rtol=1e-6, atol=1e-6)


def test_quantize_zero_vector():
    q, scale = quantize_int8(jnp.zeros(8, jnp.float32))
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(dequantize_int8(q, scale)) == 0.0)


def test_compressed_psum_single_shard_is_fake_quantize():
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.linspace(-1.0, 1.0, 64, dtype=np.float32))
    out = jax.shard_map(lambda v: compressed_psum(v, "data"), mesh=mesh,
                        in_specs=(P(),), out_specs=P(), check_vma=False)(x)
    q, scale = quantize_int8(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dequantize_int8(q, scale)),
                               rtol=0, atol=1e-6)


def test_compressed_psum_tree_structure_preserved():
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.ones((4, 4), jnp.float32),
            "b": jnp.full((4,), -2.0, jnp.float32)}
    out = jax.shard_map(lambda t: compressed_psum(t, "data"), mesh=mesh,
                        in_specs=(P(),), out_specs=P(), check_vma=False)(tree)
    assert set(out) == {"w", "b"}
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0, atol=1e-2)
    np.testing.assert_allclose(np.asarray(out["b"]), -2.0, atol=1e-1)


def test_psum_exact_integers_stay_exact():
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"bumps": jnp.asarray(3, jnp.int64),
            "counts": jnp.asarray([1, 2, 3], jnp.int32)}
    out = jax.shard_map(lambda t: psum_exact(t, "data"), mesh=mesh,
                        in_specs=(P(),), out_specs=P(), check_vma=False)(tree)
    assert int(out["bumps"]) == 3
    assert out["bumps"].dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(out["counts"]), [1, 2, 3])
