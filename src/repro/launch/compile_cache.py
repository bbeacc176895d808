"""Where the command-line entry points keep JAX's persistent compile cache.

Call ``enable_compile_cache()`` from a ``main`` before the first compile,
never at import: a library import must not change global JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py).
# A fixed path: a cache directory that moves between runs never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
