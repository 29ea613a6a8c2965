"""JAX's persistent compilation cache, as the entry points set it up.

The cache is keyed by its directory as well as by the program, so it lives
at one fixed path: ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads the variable itself, and nothing here overrides it), and otherwise
``<checkout>/.jax_cache``, which ``.gitignore`` lists.  Entry points call
``enable_compile_cache`` before they compile anything; importing the
library never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
