"""JAX's persistent compilation cache at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once, before their first compile; importing
the library never turns the cache on.  ``JAX_COMPILATION_CACHE_DIR``, when
set, names the directory and nothing overrides it; otherwise the cache is
``<checkout>/.jax_cache``.  The path is part of every entry's key, so it is
never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> Path:
    """Where compiled programs are cached: the env var, else the checkout's
    ``.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at :func:`cache_dir`."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
