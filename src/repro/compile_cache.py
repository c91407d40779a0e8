"""Where this repo's entry points keep JAX's persistent compilation cache.

A compile cache is keyed by its path, so the path is fixed: the directory
named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself, so nothing else is configured), else ``.jax_cache`` at the
repo root.  Entry points call :func:`use_compile_cache` once at start-up;
importing :mod:`repro` never touches the cache.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "compile_cache_dir", "use_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """The cache directory the entry points use."""
    return os.environ.get(CACHE_ENV) or str(REPO_ROOT / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX at :func:`compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
