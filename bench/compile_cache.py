"""JAX's persistent compilation cache for benchmark runs.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache is
the fixed directory ``.jax_cache/`` at the root of the checkout.  The path
is never built from a temporary name, a process id or the time, so a second
run in the same checkout finds what the first compiled.  Every compile is
kept, however fast: the score-reduce kernels compile in well under JAX's
default one-second threshold.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def use_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
