"""Where JAX keeps its persistent compilation cache for this repository.

The cache key includes the directory, so the path must not move between
runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
that variable itself) or the fixed ``<repo>/.jax_cache``, which
``.gitignore`` lists.  Entry points call :func:`enable_compile_cache` at
the start of ``main()``; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
