"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, this module
sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what makes a later process find an
entry again.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

__all__ = ["CHECKOUT_CACHE_DIR", "compile_cache_off", "use_compile_cache"]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Entry points call this once, before they compile anything."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


@contextlib.contextmanager
def compile_cache_off():
    """Compile without the persistent cache inside the block: nothing is
    read from it or written to it, so a compile timed there is cold."""
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()
