"""JAX's persistent compilation cache for the program's entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory in the checkout (git-ignored): a later run finds its
# entries only if it looks in the same place
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when set,
    names the directory (JAX reads it itself, and no other path is set
    here); otherwise the cache lives at ``CHECKOUT_CACHE``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
