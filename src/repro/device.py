"""Device set-up for entry points: where JAX keeps its compile cache.

Entry points call ``use_compile_cache()`` before their first compile.
Importing ``repro`` (or this module) changes nothing; the call does.
"""
from __future__ import annotations

import os
import pathlib

# A fixed path inside the checkout: the cache directory is part of what a
# later process must find again, so it never holds a temp name, pid or time.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it stands. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
