"""Persistent XLA compilation cache placement.

Compiling the join's device programs is a large part of a cold run on a
chip, so entry points (``chip_smoke.py``, the launchers' ``main()``) turn
JAX's persistent cache on through :func:`enable_compile_cache`. Nothing
calls it at import time: a library user keeps whatever cache policy their
process already has.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` — the path is part of the cache key, so a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the default cache directory: ``.jax_cache`` at the root of the checkout
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        # JAX picked the variable up when its config was initialised
        return env
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
