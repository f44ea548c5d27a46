"""Where JAX keeps its persistent compilation cache for this checkout."""
from __future__ import annotations

import os
from pathlib import Path

#: the cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is not set
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself, and nothing here overrides it); otherwise the cache lives at
    ``<checkout>/.jax_cache``. The path is part of the cache key, so it
    is fixed: a later run from the same checkout finds what this one
    compiled. Call it from a program's entry point, before the first
    compile — never at import time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
