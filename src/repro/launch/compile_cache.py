"""Where JAX's persistent compilation cache lives.

Every entry point that compiles (``serve_miner``, ``launch.mine``,
``chip_smoke.py``) calls :func:`configure_compile_cache` before its first
compile. The cache key includes the directory, so the directory is fixed:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code, so the cache is written there and nowhere else;
* otherwise: ``<checkout>/.jax_cache`` (git-ignored) — one fixed path per
  checkout, never derived from a temp name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "configure_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
