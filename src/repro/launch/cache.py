"""Where the entry points keep JAX's persistent compilation cache.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py`` and the examples) call :func:`configure_compile_cache`
before their first compile; importing the library never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``:
a path that never moves between runs, so a later process finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
