"""JAX's persistent compilation cache for the entry points that run on the chip.

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the
variable itself); without it, in the fixed ``.jax_cache/`` directory of the
checkout, which git ignores.  The path is part of what a later run must
find again, so it never depends on a temporary name, a PID or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO_ROOT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
