"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py`` and the ``benchmarks/`` scripts call
:func:`enable_compile_cache` once at program start — never a library module
on import, so tests and embedding programs keep JAX's own defaults.

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set
(JAX reads that variable itself), and otherwise at ``<repo>/.jax_cache``: a
fixed path, because the path is part of what a later run must find again.
Every compile is cached, however short: a cold chip run spends most of its
time compiling (``eigh`` at d=2304 alone takes minutes), and the many small
kernel variants add up.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the repo's."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    admit every compile into it.  Returns the directory."""
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
