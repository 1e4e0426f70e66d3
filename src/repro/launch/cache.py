"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the serve
and train CLIs) calls ``init_compile_cache`` before it compiles
anything.  A cache directory set from outside (``JAX_COMPILATION_CACHE_DIR``,
which JAX reads itself) is left alone; otherwise the cache goes to
``<checkout>/.jax_cache``.  The path is fixed -- never a temp name, pid
or time -- so the next run looks where this one wrote.  No other code
sets a cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    outside = os.environ.get(ENV_CACHE_DIR)
    if outside:
        return outside
    import jax  # noqa: PLC0415 - callers may set JAX env vars first

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
