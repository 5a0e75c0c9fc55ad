"""JAX's persistent compilation cache at a fixed place.

Entry points call :func:`use_compile_cache` before their first compile,
so a second run of the same program on the same device reads its
compiled executables back instead of compiling again.  Importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache lives at a fixed place at the root of the checkout, so every
#: run of every entry point finds what earlier runs compiled
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is set here; otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
