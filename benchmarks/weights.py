"""Random weights from the seed, made on the device in one jitted call
in the type they are served in (bf16 matrices, fp32 norm scales), as the
model's own initialisers draw them: normal(0.02), scales 1."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import traverse_util


def decoder_weights(model, seed: int):
    cfg = model.cfg
    shapes = traverse_util.flatten_dict(jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), seq=8)))
    paths = sorted(shapes)

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = {}
        for k, path in zip(keys, paths):
            leaf = shapes[path]
            if path[-1] == "weight":      # RMSNorm scale
                out[path] = jnp.ones(leaf.shape, leaf.dtype)
            else:
                out[path] = (0.02 * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(cfg.compute_dtype)
        return traverse_util.unflatten_dict(out)

    # the seed may exceed 32 signed bits: fold it in two halves
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)
