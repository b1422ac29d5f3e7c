"""Where JAX keeps its persistent compilation cache.

Every new cluster size ``(n, tp)`` compiles a new train step, and a fresh
process compiles all of them again unless a persistent cache holds them.
``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside: JAX
reads that variable itself and this module sets no other path. Otherwise the
cache lives at one fixed path inside the checkout, ``.jax_cache/``, so the
next run of the same checkout finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(default_dir: Path = DEFAULT_CACHE_DIR) -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)
