"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`place_compile_cache` once, before their first
compile; importing this module changes nothing.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache goes to ``.jax_cache`` at
the repository root: a fixed path, because the path is part of what a
later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
