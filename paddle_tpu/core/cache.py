"""Where the program keeps what it compiled and what it tuned.

One rule, applied once at package import: a cache is placed from OUTSIDE or
at one fixed path inside the checkout — never a temp name, a pid, a time or
the home directory, because the path is part of a compile-cache key and a
directory that moves never hits.

- ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it; nothing here
  touches the compile cache.
- unset: ``jax_compilation_cache_dir = <checkout>/.jax_cache``.

The kernel autotune cache (kernels/autotune.py) lives under the same root.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def cache_root() -> str:
    """The directory this process's caches live in."""
    return os.environ.get(_ENV) or _IN_CHECKOUT


def configure_compile_cache() -> None:
    """Point jax's persistent compile cache at the in-checkout path unless
    the environment already placed it. Touches config only: no backend
    starts."""
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
