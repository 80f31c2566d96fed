"""Where JAX keeps its persistent compilation cache for this checkout.

A cold run on the chip compiles every kernel and jitted program it touches;
the persistent cache lets later runs on the same machine skip that work.
A later run finds the entries only if the directory does not move: it is
either the one ``JAX_COMPILATION_CACHE_DIR`` names, or the fixed
``.jax_cache`` directory at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Call before the first compile.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
