"""Placement of JAX's persistent compilation cache for every entry point.

The cache key includes the directory, so a cache only pays off at a
path that stays put between runs. ``JAX_COMPILATION_CACHE_DIR`` wins
when it is set (JAX reads it itself, so no other directory is set in
code); otherwise the cache lives at ``<checkout>/.jax_cache``, which git
ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
