"""Persistent XLA compile cache for the command-line entry points.

``enable()`` is called from the launchers' ``main()`` and from
``chip_smoke.py``, never on ``import repro``: a library user's process
keeps whatever cache configuration it already has.

* ``JAX_COMPILATION_CACHE_DIR`` set: nothing is configured here; JAX
  reads the variable itself and caches there.
* Otherwise the cache lives at ``<checkout>/.jax_cache/``.  The path is
  fixed (never a temporary name, PID or timestamp) because it is part
  of the cache key: a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
