"""Where JAX keeps its persistent compilation cache.

Entry scripts (``chip_smoke.py``, ``benchmarks.run``, the examples,
``repro.launch.simulate``) call :func:`enable_compile_cache` once, right
after importing JAX and before anything compiles.  The library itself never
touches the cache setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and keeps
  its cache there; nothing else is configured.
* Not set: the cache goes to ``.jax_cache/`` at the root of this checkout —
  one fixed path, so a second run finds what the first compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
