"""Where the program's entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives in ``<repo>/.jax_cache``: a fixed path, so
a later run of the same checkout finds what an earlier one compiled (the
directory is part of the cache's key, so a temp name would never hit). Tests
do not call this.
"""
from __future__ import annotations

import os

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
