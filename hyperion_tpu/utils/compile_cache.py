"""Where JAX's persistent compilation cache lives — decided outside.

One rule for every entry point (trainer CLI, `serve`,
`benchmarks/run.py`, `chip_smoke.py`): where `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it itself and the program sets nothing; where it is not, the
cache goes to `<repo>/.jax_cache` (git-ignored). The path is part of
the cache's key, so it must not move between runs. Children inherit
the environment variable; there is no flag and no second name. The
checkout's own directory is kept OUT of the key: see below.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = ROOT / ".jax_cache"


def place_compile_cache() -> str:
    """Returns the directory the cache is kept in."""
    import jax

    # A program that holds a Pallas kernel carries the kernel's own
    # module in its custom call, source locations and all, and the
    # cache's key hashes that text: every file name in it would begin
    # with the checkout's directory, and a checkout that moved would
    # find none of its entries (PERF.md section 6, PR 21 and PR 27).
    # Name the files from the checkout's root instead.
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(str(ROOT)) + "/")
    outside = os.environ.get(ENV)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
