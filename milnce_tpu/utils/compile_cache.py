"""One rule for JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this code
sets no directory, so whoever runs the program places the cache.  Where
it is not, the cache lives at a FIXED path: the directory is part of the
cache key, so one made from a temporary name, a process id or the time
would never hit.  Every entry point calls :func:`configure_compile_cache`
before its first compile; nothing else in the repo names a cache
directory (the tests pass their own fixed path as ``default_dir``).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, "build", "jax_cache")


def configure_compile_cache(default_dir: str = DEFAULT_CACHE_DIR) -> str:
    """Enable the persistent cache; returns the directory in effect."""
    import jax

    # cache every program: a cold chip call recompiles the whole S3D-G
    # step otherwise, and the small programs around it cost nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
