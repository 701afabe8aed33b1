"""The persistent XLA compile cache shared by every entry point."""
from __future__ import annotations

import os

import jax

#: the checkout that holds this package (``<checkout>/src/repro/launch``)
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        '..', '..', '..'))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``, never in a temporary directory or under a
    pid or a time: a cache whose directory moves is never found again."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    path = os.path.join(CHECKOUT, '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path
