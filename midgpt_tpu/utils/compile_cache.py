"""Where JAX's persistent compilation cache lives, decided in one place.

The directory is part of every cache key, so one that moves (a temporary
name, a pid, a time) never hits. Entry points call
:func:`enable_compile_cache` before their first compile."""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (git-ignored): fixed for a given checkout
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it
    and no directory is set in code, so whoever runs the program can
    place the cache; otherwise it is the fixed path in the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
    return _IN_CHECKOUT
