"""Where the persistent XLA compile cache lives, for every entry point.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where the caller set it,
the cache lives there and no path is set in code. Otherwise it lives at
the fixed ``<checkout>/.jax_cache`` — the directory is part of the
cache's key, so it must not move between runs."""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the verdict kernels shape-bucket their tables, so nearly every
    # jit is worth keeping, however small or quick
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    return path
