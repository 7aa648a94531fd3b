"""JAX's persistent compilation cache, set up in one place.

Entry points call ``enable_compile_cache()`` before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing else is set; otherwise the cache lives at one fixed
directory inside the checkout (listed in ``.gitignore``). A fixed path
matters: the directory is part of what a later process looks up, so a
temporary or per-run name would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
