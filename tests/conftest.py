import os

# Tests run on the host CPU with a single device (the dry-run sets its own
# device count in a separate process).  x64 is enabled because the GLU
# numeric oracles and circuit simulation are validated in float64.
os.environ.setdefault("JAX_ENABLE_X64", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

import jax


_TESTS_SINCE_CLEAR = 0


@pytest.fixture(autouse=True)
def _bounded_xla_code_accumulation():
    """Keep the compiled code one test process accumulates bounded: every
    plan compiles its own whole-schedule programs, and a long suite in one
    process otherwise piles up hundreds of CPU executables.  Dropping the
    executable caches every 64 tests costs a handful of recompiles."""
    global _TESTS_SINCE_CLEAR
    yield
    _TESTS_SINCE_CLEAR += 1
    if _TESTS_SINCE_CLEAR >= 64:
        _TESTS_SINCE_CLEAR = 0
        jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
