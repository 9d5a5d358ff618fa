"""Persistent XLA compilation cache for the entry points.

Called from the ``main()`` of ``launch/train.py`` and from
``chip_smoke.py`` — never at import. ``JAX_COMPILATION_CACHE_DIR``,
when set, is JAX's own setting and wins; otherwise the cache lives at the
fixed, git-ignored ``<checkout>/.jax_cache``. The path is part of the
cache key, so it never depends on a pid, a clock or a temp directory.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
