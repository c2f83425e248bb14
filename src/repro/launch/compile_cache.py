"""JAX's persistent compilation cache for the serving entry points.

A cold process on a TPU spends minutes compiling the engine programs; the
persistent cache lets a later process with the same programs skip that.
The cache key includes the directory, so it lives at one fixed place:

  * where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and nothing here
    overrides it;
  * otherwise the cache lives at `.jax_cache/` in this checkout (listed in
    .gitignore), never at a path built from a temp name, pid or time.

Called by chip_smoke.py, launch/serve.py and the benchmark mains -- never
by tests or at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
