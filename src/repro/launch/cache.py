"""Placement of JAX's persistent compilation cache for the entry points.

Called from an entry point's ``main`` (never at import): where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing is set here;
otherwise the cache goes to the fixed path ``<repo root>/.jax_cache``
(git-ignored).  The path is part of the cache key, so it must not move
between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
