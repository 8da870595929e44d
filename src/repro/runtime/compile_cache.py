"""Where JAX's persistent compilation cache lives.

One rule for every entry point (the serving CLI, the benchmark runner,
`chip_smoke.py`): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
uses it and nothing else is set; otherwise the cache goes to one fixed
path inside the checkout, ``.jax_cache/`` (git-ignored). The path is part
of the cache key, so it must not move between runs. Entry points call
`configure_compile_cache()` from `main`; importing the library sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent cache at its one directory; returns it."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
