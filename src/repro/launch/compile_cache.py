"""Where JAX keeps its persistent compilation cache.

Both entry points (``repro.launch.serve`` and ``chip_smoke.py``) call
:func:`configure_compile_cache` before their first compile.  If the
environment sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads it by itself and
nothing is set in code.  Otherwise the cache lives at one fixed directory
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): the
path is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
