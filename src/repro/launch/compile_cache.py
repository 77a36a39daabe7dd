"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` before their first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache sits at one fixed path inside the
checkout (``<repo>/.jax_cache``, gitignored): the directory is part of
what a later run looks up, so it never carries a temp name, a pid or a
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it.

    The cache key covers each operation's metadata (its ``jax.named_scope``
    path and source line), so an executable read back from the cache names
    its operations as its own program does in a profile, not as another
    program that differs from it only there."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
