"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
:func:`use_compile_cache` when they start — never at import — so a library
import or a test run leaves JAX's configuration alone.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache and JAX reads it
itself.  Otherwise the cache lives at one fixed path inside the checkout
(``.jax_cache/``, ignored by git): the directory is part of a cached entry's
identity, so a path built from a temp name, a pid or the time would never
hit.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
