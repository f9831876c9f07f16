"""Where JAX keeps its persistent compilation cache.

`JAX_COMPILATION_CACHE_DIR`, when set, is the cache, and no other
directory is set in code.  Otherwise the cache lives at a
fixed path inside the checkout, `<repo>/.jax_cache` (git-ignored), so
that every run of the same checkout finds what earlier runs compiled;
the path never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) before the first compile; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
