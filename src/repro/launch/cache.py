"""JAX's persistent compilation cache for the program's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing else is configured.  Otherwise the cache lives in one fixed
directory inside the checkout (``.jax_cache``, git-ignored).  The path
is part of what a cache entry is found by, so it must not depend on a
temporary name, a pid or the time: a later process of the same
checkout then finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory: <root>/.jax_cache, where <root>
#: holds ``src/repro``
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns the directory
    it configured, or ``None`` when the environment variable already
    names one."""
    if os.environ.get(ENV):
        return None
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
