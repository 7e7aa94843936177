"""One place for JAX's persistent compilation cache.

The path is part of the cache key, so it must not move between runs: no
temporary directory, process id or time stamp in it.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives in ``<checkout>/.jax_cache``
(listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed in-checkout cache directory used when the environment names none
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and return
    it.  Call before anything compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
