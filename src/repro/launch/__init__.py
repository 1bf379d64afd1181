"""Launch layer: production meshes, dry-run, train/serve/query drivers."""

from __future__ import annotations

import os
from pathlib import Path

# Fixed, so that every run from this checkout finds the entries the last
# one wrote (the cache directory is part of what keys an entry).
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, and
    no other directory is set); otherwise the cache lives at
    :data:`DEFAULT_COMPILE_CACHE`, inside the checkout.  Call it before
    the first compile.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
