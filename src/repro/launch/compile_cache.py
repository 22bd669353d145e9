"""Placement of JAX's persistent compilation cache, shared by every
entry point (``launch.serve``, ``launch.train``, ``benchmarks.run`` and
``chip_smoke.py``).

``JAX_COMPILATION_CACHE_DIR``, when set, wins and no other directory is
configured. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what a later process must find again, so it never depends on a
temp directory, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) and cache every compiled program, however
    quick its compile. Call before the first compile; returns the
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
