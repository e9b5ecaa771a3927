"""JAX's persistent compilation cache for the repo's entry-point scripts.

Scripts call `enable_compilation_cache()` first thing in `main`; library
modules never do, so importing the package changes no JAX setting."""

from __future__ import annotations

import os
import pathlib

import jax

# <repo>/src/repro/launch/compile_cache.py -> <repo>/.jax_cache (git-ignored).
# A fixed path: the directory is part of what makes a later run hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is honoured as is (JAX reads it
    itself); otherwise the cache lives at the checkout's `.jax_cache/`.

    The cache key includes each program's op metadata: by default JAX
    strips it, so a program whose source differs only in its trace scopes
    (`repro.serve.tracing`) would load an executable built without them,
    and its device time would fall outside every stage of a trace."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
