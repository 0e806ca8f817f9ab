"""JAX's persistent compilation cache for the entry points.

Each entry point calls ``enable()`` once at start-up; importing this
module changes nothing, and the tests never call it. The cache path is
part of the cache's key, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this sets
nothing, otherwise the cache lives at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
