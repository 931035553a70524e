"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called first thing in each ``main()``
(``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) and never at import time.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and nothing
is set here.  Otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of what lets a later
run find an entry again (git ignores the directory).
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring); returns the directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
