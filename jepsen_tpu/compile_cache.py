"""JAX's persistent compilation cache, placed from outside.

Entry points (the CLI, ``bench.py``, ``chip_smoke.py``, the live and
fleet daemons) call :func:`enable` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the directory is part of what a later
process looks up, so it is never built from a temporary name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable() -> str:
    """Turns the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
