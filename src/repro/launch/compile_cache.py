"""JAX's persistent compilation cache, placed from outside the code.

Entry points (``chip_smoke.py``, ``repro.launch.crawl``,
``repro.launch.serve_search``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; importing this module changes
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
no other directory is set here. Otherwise the cache is the checkout's
``.jax_cache/`` — a fixed path, so a later run finds what an earlier one
compiled (the path is part of the cache key).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
