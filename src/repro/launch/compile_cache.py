"""The persistent XLA compile cache shared by the launchers.

A cold process on a TPU recompiles every program, which for a full train
step takes a minute.  :func:`use_compile_cache` lets later processes
reuse what earlier ones compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured.  Otherwise the cache lives at the
    repository's fixed ``.jax_cache``: the directory is where entries are
    looked up, so a temporary or per-process path would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
