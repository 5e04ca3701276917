"""JAX's persistent compilation cache, placed before the first compile.

A compile of the full-size rollout takes tens of seconds per padded
shape, and a fresh process pays it again unless the executable is cached
on disk.  An entry is found again only in the directory it was written
to, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (and no other), else
the fixed, git-ignored ``.jax_cache/`` at the repository root.  Entry
points (``chip_smoke.py`` and the ``benchmarks/`` CLIs) call
:func:`enable_compile_cache` before they compile anything; library code
never does.
"""

from __future__ import annotations

import os

import jax

#: Environment variable that, when set, names the only cache directory.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: The fixed default: ``<repo>/.jax_cache`` (this file is
#: ``<repo>/src/repro/compile_cache.py``).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache lives in (see the module docstring)."""
    return os.environ.get(ENV_CACHE_DIR) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
