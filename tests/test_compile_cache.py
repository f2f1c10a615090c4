"""Where the entry scripts put JAX's persistent compilation cache."""
import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_restored, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_default_is_the_fixed_checkout_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(CHECKOUT_CACHE_DIR) == enable_compile_cache()
    assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == first
