"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``)
call ``use_compile_cache()`` once, before their first compile.  Library
modules and tests never do.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache: fixed, so a later run finds what an earlier
# one compiled (the path is part of the cache key); listed in .gitignore
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use.  If ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is changed here; otherwise
    the cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
