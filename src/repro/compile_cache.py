"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, gitignored directory at the root of the checkout: an entry is only
# found again under the same path, so it is never built from a temp name,
# a pid or the time
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache lives
    in ``CHECKOUT_CACHE_DIR``.  Every compile is kept, however fast: the
    score-reduce kernels compile in well under JAX's default one-second
    threshold, and a run compiles dozens of them.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
