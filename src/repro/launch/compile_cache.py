"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks.run``) call ``enable_compile_cache()`` from their ``main()``;
importing a package never turns the cache on, so tests that compile for
a described (unattached) TPU stay cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path, never one built from a temp name, a pid or the time: a
# cache directory that moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself; no other
    directory is set), else ``<repo>/.jax_cache``.  Every program is
    cached, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
