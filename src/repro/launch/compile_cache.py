"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`enable` at the top of a script (``chip_smoke.py``, the
examples, ``benchmarks/run.py``); library modules never call it.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself and
nothing here overrides it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (ignored by git): the path is part of the cache
key, so a directory that moves between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
