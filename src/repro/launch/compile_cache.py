"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout: the cache is only found again
# by a run that points at the same path
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and no other directory is set; otherwise the
    cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
