"""Placement of JAX's persistent compilation cache.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `python -m
repro.serve`) call `enable()` once at start-up; nothing calls it at import,
so tests and library users keep whatever cache settings they chose.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# The checkout root (this file is src/repro/compile_cache.py). A fixed path:
# the cache directory is part of what a later run must find again.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory:
    `JAX_COMPILATION_CACHE_DIR` when set (and no other), else
    `<checkout>/.jax_cache`. The directory is written to the config either
    way, since JAX reads the variable only when it is imported. Every
    executable is cached, however quick its compile, so a warm run
    recompiles nothing."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
