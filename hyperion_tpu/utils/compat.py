"""Two helpers over `jax.shard_map`'s varying-axes (vma) typing, shared
by the ring-attention and pipeline loops. The tree runs on one JAX (the
line pinned in pyproject.toml); everything else that once lived here was
a branch for a release that is not installed, and callers now use
`jax.shard_map`, `jax.lax.axis_size` and `pltpu.CompilerParams` directly.
"""

from __future__ import annotations

import jax


def vma_of(x) -> tuple:
    """Mesh axes `x` varies over inside shard_map."""
    return tuple(jax.typeof(x).vma)


def pvary(x, axes: tuple):
    """Cast `x` to vary over `axes`, so a loop carry type-checks against
    a body whose output varies; identity when no axes are requested."""
    if not axes:
        return x
    return jax.lax.pcast(x, axis_name=axes, to="varying")
