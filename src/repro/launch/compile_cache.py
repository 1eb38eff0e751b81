"""JAX's persistent compilation cache for the launchers.

Compiling the served model at full width takes most of a cold start, and a
cache only hits when its directory stays put (the path is part of what JAX
looks up).  So: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing is set here; otherwise the cache lives at the fixed
``.jax_cache/`` in the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
