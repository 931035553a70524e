import os
import sys

import pytest

# Tests run against the source tree (PYTHONPATH=src also works).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# NOTE: no XLA_FLAGS here on purpose — smoke tests must see 1 device.
# Multi-device tests spawn subprocesses that set the flag themselves.


@pytest.fixture(autouse=True)
def _compile_cache_dir_restored():
    """Entry points turn JAX's persistent compile cache on
    (``repro.launch.compile_cache``); a test that calls one must not
    leave it on for the tests after it in the same worker."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
