"""Where JAX keeps its persistent compilation cache, decided in one place.

Every entry point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks/common.py``) calls ``enable_compile_cache`` before it compiles.
``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else is
configured.  Otherwise the cache lives at ``.jax_cache/`` in the checkout —
a fixed path, because the directory is part of what a cached entry is found
by, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR``, else the
    checkout's ``.jax_cache/``."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that path.  With the environment variable set JAX already
    reads it, so no directory is set here."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
