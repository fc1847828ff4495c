"""Persistent XLA compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``
(git-ignored).  The directory is fixed on purpose: the path is part of
what a run looks up, so a per-run name (temporary directory, pid, time)
would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    Call before the first compilation (entry points call it first)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
