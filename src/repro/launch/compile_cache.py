"""Persistent XLA compile cache for the program's entry points.

Entry points (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/serve_bench.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` from their ``main``; nothing calls it at
import, so library users and the tests never write a cache.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the cache directory is part of the
# cache key, so a path that moved between runs would never hit
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that itself, so
    nothing is set here), else ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
