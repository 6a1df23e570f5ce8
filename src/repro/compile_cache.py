"""Persistent JAX compile cache for the repo's entry points.

Scripts call :func:`enable_compile_cache` when they start; no library
module calls it, so importing ``repro`` never changes JAX's configuration.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this leaves it
alone.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because a cache directory that moves from
run to run is never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: <checkout>/.jax_cache — this file is <checkout>/src/repro/compile_cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
