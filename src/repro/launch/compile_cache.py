"""Where JAX keeps its persistent compilation cache.

A cold chip run compiles every program it runs; the persistent cache
lets later processes (benchmark figures, the smoke run) load what an
earlier one compiled.  The cache directory is part of each entry's
lookup, so it must not move between runs: it is either the directory
``JAX_COMPILATION_CACHE_DIR`` names or ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and nothing
    is set here.  Otherwise the variable is set to ``<repo>/.jax_cache``,
    so this process (if it imports JAX later) and every child process it
    starts share that one directory; a JAX already imported is pointed at
    it directly."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
