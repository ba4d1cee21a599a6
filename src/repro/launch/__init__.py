"""Launchers: production mesh construction, multi-pod dry-run, train/solve drivers."""

from __future__ import annotations

import os

#: the checkout root (``src/repro/launch/__init__.py`` -> three levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives (JAX
    reads it itself; nothing else is set).  Otherwise the cache is the
    fixed ``<repo>/.jax_cache``: the directory is part of each entry's
    key, so a later run of the same program only hits it at the same path.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
