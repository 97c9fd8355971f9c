"""JAX's persistent compilation cache, at one fixed place.

Compiling the kernels and the serve step at full size takes minutes; the
persistent cache lets a later process on the same machine skip it.  A
later process finds the entries only in the same directory, so no temp
name, pid or time goes into it.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: The environment variable JAX reads the cache directory from.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — git-ignored.
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
