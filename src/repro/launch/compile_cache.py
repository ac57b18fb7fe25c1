"""Where JAX keeps its persistent compilation cache: one rule for every entry.

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``). The directory is
never derived from a temp path, a pid or the time: a cache that moves
never hits.

Call ``enable_compile_cache()`` at the top of an entry point's ``main``,
before the first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Every compilation is cached, however short: the search's kernels
    compile in a second or two each, which the default threshold skips.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
