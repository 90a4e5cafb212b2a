"""Persistent XLA compilation cache for the entry points.

Called by the command-line entry points (``repro.launch.train``,
``repro.launch.serve``) and ``chip_smoke.py`` before their first compile;
never on import, so library users and tests keep JAX's own defaults.
"""
from __future__ import annotations

import os

import jax

# a fixed path at the checkout root: the path is part of the cache key, so a
# directory that moved between runs would never hit
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                         "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory used.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left alone; otherwise the cache goes to ``.jax_cache/`` at the checkout
    root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
