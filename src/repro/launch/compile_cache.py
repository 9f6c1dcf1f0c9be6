"""Persistent XLA compilation cache for the repo's entry-point scripts.

``chip_smoke.py``, ``benchmarks/run.py`` and the ``examples/`` scripts call
:func:`enable_compile_cache` once at start-up; the library and the test
suite never do, so tests that compile for a described chip stay silent.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory is the only one used.  Otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (gitignored): the path is
part of what a later process must find again, so it never carries a
temporary name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
