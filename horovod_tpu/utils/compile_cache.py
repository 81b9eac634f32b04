"""JAX's persistent compilation cache at a path that does not move.

The cache directory is part of the cache key, so it must be the same in
every process of a checkout: where ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it and this module sets nothing; otherwise the cache lives in
``<checkout>/.jax_cache`` (gitignored), derived from this file's path.
"""

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable():
    """Point JAX at the persistent cache; returns the directory in use.
    Call before the first compilation."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
