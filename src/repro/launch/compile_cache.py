"""Persistent XLA compilation cache for the entry points.

Entry points (``launch.serve``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start-up; importing this module
changes nothing, and tests never call it. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at one fixed path in the
checkout, so the next run finds it: a temp, per-pid or per-time
directory would never be read again.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
