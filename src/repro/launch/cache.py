"""JAX's persistent compilation cache for the entry points."""
from __future__ import annotations

import os
import pathlib

import jax

# the checkout this package runs from (src/repro/launch/cache.py)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``,
    a fixed path, so that the next run finds what this one compiled.
    Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
