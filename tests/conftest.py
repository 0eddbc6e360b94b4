"""Shared fixtures.  NOTE: no XLA_FLAGS here -- smoke tests and benches
must see the single real CPU device; only dryrun.py forces 512.
"""

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _bounded_compile_cache():
    """Drop jit/pjit compile caches at module boundaries.

    A full tier-1 run compiles thousands of distinct programs in one
    process; on single-CPU containers the accumulated executables
    eventually segfault XLA:CPU inside a late ``backend_compile``
    (the failing test roams -- whichever module compiles next once
    the process is saturated).  Clearing at module boundaries keeps
    the footprint bounded; recompilation is deterministic, so
    numerics are unaffected.
    """
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
