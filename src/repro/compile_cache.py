"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`use_compile_cache` once before they compile anything; library
modules never set a cache directory.  ``JAX_COMPILATION_CACHE_DIR`` wins
when it is set.  Otherwise the cache lives at a fixed path inside the
checkout: the directory is part of each entry's key, so a path derived
from a temp dir, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the path used."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
