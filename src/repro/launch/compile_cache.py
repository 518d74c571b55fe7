"""Persistent XLA compilation cache for the entry points.

Called from an entry point's ``main()``, never at import.  A cache hit
needs the same directory on every run (the path is part of the key), so
the default is a fixed directory in the checkout, never a temp name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache directory: ``<repo>/.jax_cache`` (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
