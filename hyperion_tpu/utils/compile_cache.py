"""Where JAX's persistent compilation cache lives — decided outside.

One rule for every entry point (trainer CLI, `serve`, `bench.py`
children, `chip_smoke.py`): where `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it itself and the program sets nothing; where it is not, the
cache goes to `<repo>/.jax_cache` (git-ignored). The path is part of
the cache's key, so it must not move between runs. Children inherit
the environment variable; there is no flag and no second name.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Returns the directory the cache is kept in."""
    outside = os.environ.get(ENV)
    if outside:
        return outside
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
