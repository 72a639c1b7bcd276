"""JAX's persistent compilation cache for the program's entry points.

The chip smoke, the benchmark harness and ``python -m repro.service`` call
:func:`enable_compile_cache` once at start-up (never on import), so a
second run in the same checkout reuses the compiled kernels and steps of
the first.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting
and is left alone; otherwise the cache lives in ``<checkout>/.jax_cache`` —
a fixed path, since the cache directory is part of every entry's key.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root: ``src/repro/compile_cache.py`` → three levels up
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
