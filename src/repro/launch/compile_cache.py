"""JAX's persistent compilation cache, kept at one fixed path.

A run finds what an earlier one compiled only when both use the same
directory; this module pins it to ``.jax_cache/`` at the root of the
checkout unless the environment already names one.
"""

from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root, three up.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``DEFAULT_DIR``.
    Call it before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
