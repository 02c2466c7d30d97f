"""JAX's persistent compilation cache, at one fixed place per checkout.

A cache is found again only at the path it was written to, so the path is
fixed: the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX
reads it itself, and nothing here sets another), and otherwise
``<checkout>/.jax_cache`` — never a path built from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: Path) -> Path:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    path = Path(checkout).resolve() / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
