"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`configure_compile_cache` once, before their
first compile; nothing calls it at import time, and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
    other directory is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache/``: a fixed path, because the path is part of
    what makes a later run find the entries again. Returns the directory
    in use."""
    import jax
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
