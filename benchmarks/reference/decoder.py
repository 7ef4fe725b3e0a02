"""Plain reference of the served decoder: RMSNorm, rotary positions
(half-split pairing, as Hugging Face's `rotate_half`), grouped-query
causal attention and SwiGLU in `jax.numpy`, float32, `highest` matmul
precision. No cache, no kernels, no batching tricks: one full forward
over whole sequences. Independent of `hyperion_tpu.models.llama` but for
the names of the weights it is handed."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, T, H, D] at positions 0..T-1."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@partial(jax.jit, static_argnames=("theta", "eps"))
def layer(x, p, *, theta, eps):
    """One block on x [B, T, d]; `p` is the layer's weights in any float
    type, upcast here so only one layer is ever held in float32."""
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["input_norm"]["weight"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, p["attn"]["q_proj"]["kernel"])
        k = jnp.einsum("btd,dhk->bthk", h, p["attn"]["k_proj"]["kernel"])
        v = jnp.einsum("btd,dhk->bthk", h, p["attn"]["v_proj"]["kernel"])
        q, k = rope(q, theta), rope(k, theta)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(q.shape[-1])
        T = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("bqhk,hkd->bqd", a, p["attn"]["o_proj"]["kernel"])
        h = rms_norm(x, p["post_attn_norm"]["weight"], eps)
        g = h @ p["mlp"]["gate_proj"]["kernel"]
        u = h @ p["mlp"]["up_proj"]["kernel"]
        return x + (jax.nn.silu(g) * u) @ p["mlp"]["down_proj"]["kernel"]


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, out_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_w.astype(jnp.float32), eps) @ \
            out_w.astype(jnp.float32)


def logits(params: dict, ids, *, n_layers: int, theta: float, eps: float):
    """ids int32 [B, T] -> float32 [B, T, vocab]."""
    x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for i in range(n_layers):
        x = layer(x, params[f"layer_{i}"], theta=theta, eps=eps)
    return head(x, params["final_norm"]["weight"],
                params["lm_head"]["kernel"], eps=eps)


def token_slack(ref_logits, sequences) -> float:
    """How far below its row's best reference logit the worst served
    token lies, in standard deviations of the checked rows. `sequences`
    is [(prompt_len, n_generated)] per row of `ref_logits`, whose ids
    were prompt + generated, padded. Row p-1+i predicts generated i."""
    import numpy as np

    rows, toks = [], []
    for b, (p, g, ids) in enumerate(sequences):
        rows.append(np.asarray(ref_logits[b, p - 1: p - 1 + g]))
        toks.append(np.asarray(ids[p: p + g]))
    rows, toks = np.concatenate(rows), np.concatenate(toks)
    below = rows.max(-1) - rows[np.arange(len(toks)), toks]
    return float(below.max() / rows.std())
