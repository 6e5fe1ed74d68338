"""Where JAX keeps its persistent compilation cache.

Entry points (``serve.main``, ``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once, before anything compiles; importing a module
of this package never touches the cache.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is left to JAX.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of a cache entry's key, so a
directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
