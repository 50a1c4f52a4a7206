"""JAX's persistent compilation cache, as the repo's entry points use it.

A cold run on a TPU spends much of its time compiling; the persistent cache
lets a second process (or a second run on the same machine) load the
compiled programs instead. A run finds what an earlier run wrote only in
the same directory, so the directory must not move between runs: it is
either the one the environment names or a fixed path in the checkout, never
one made from a temporary name, a process id or the time.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``, the
benchmark and example ``__main__`` blocks); importing ``repro`` never turns
the cache on, so the test suite runs without it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    ``jax_compilation_cache_dir`` and no other directory is set here.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored)."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
