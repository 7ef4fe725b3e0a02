"""Plain reference of the trained language model and of its optimizer:
GPT-2's block (learned positions, pre-LayerNorm, biased projections, MLP
with the tanh form of GELU, GPT-2's `gelu_new`, which is what
`flax.linen.gelu` gives by default), the next-token loss, its gradient by
`jax.grad`, global-norm clipping and AdamW written out, all in
`jax.numpy`, float32, `highest` matmul precision, no dropout.
Departures from GPT-2 that the system makes and this follows (listed in
the configuration file): untied output head with a bias, LayerNorm eps
1e-6. Independent of `hyperion_tpu` and `optax` but for the names of the
weights it is handed. Below it, the comparisons that hold the system to
it."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

EPS = 1e-6


def layer_norm(x, p):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + EPS) * p["scale"] + p["bias"]


def block(x, p, causal):
    h = layer_norm(x, p["ln1"])
    a = p["attn"]
    q = jnp.einsum("btd,dhk->bthk", h, a["q_proj"]["kernel"]) + a["q_proj"]["bias"]
    k = jnp.einsum("btd,dhk->bthk", h, a["k_proj"]["kernel"]) + a["k_proj"]["bias"]
    v = jnp.einsum("btd,dhk->bthk", h, a["v_proj"]["kernel"]) + a["v_proj"]["bias"]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(q.shape[-1])
    if causal:
        T = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, a["o_proj"]["kernel"]) \
        + a["o_proj"]["bias"]
    h = layer_norm(x, p["ln2"])
    h = jax.nn.gelu(h @ p["fc1"]["kernel"] + p["fc1"]["bias"], approximate=True)
    return x + h @ p["fc2"]["kernel"] + p["fc2"]["bias"]


def _logits(params, ids, causal):
    params = jax.tree.map(lambda w: w.astype(jnp.float32), params)
    n_layers = sum(k.startswith("block_") for k in params)
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"]["embedding"][ids] \
            + params["pos_emb"]["embedding"][:ids.shape[1]][None]
        for i in range(n_layers):
            x = block(x, params[f"block_{i}"], causal)
        x = layer_norm(x, params["ln_f"])
        return x @ params["lm_head"]["kernel"] + params["lm_head"]["bias"]


def _loss(params, ids, causal):
    lp = jax.nn.log_softmax(_logits(params, ids, causal)[:, :-1], -1)
    return -jnp.take_along_axis(lp, ids[:, 1:, None], -1).mean()


@partial(jax.jit, static_argnames=("causal",))
def logits(params, ids, causal=True):
    """float32 [B, T, vocab] of ids int32 [B, T]. `causal=False` is not
    the model: it is there so that a test can show what a forward pass
    without its mask does to the comparisons below."""
    return _logits(params, ids, causal)


@partial(jax.jit, static_argnames=("causal",))
def loss(params, ids, causal=True):
    """Mean next-token cross-entropy of ids int32 [B, T]."""
    return _loss(params, ids, causal)


_loss_and_grad = jax.jit(jax.value_and_grad(_loss), static_argnames=("causal",))


@jax.jit
def _adamw(params, mu, nu, grads, t, lr, wd, clip, b1=0.9, b2=0.999, eps=1e-8):
    """Gradients clipped to a global norm of `clip` (0: not clipped), then
    AdamW with bias correction and decoupled weight decay."""
    norm = jnp.sqrt(sum((g * g).sum() for g in jax.tree.leaves(grads)))
    scale = jnp.where((clip > 0) & (norm > clip), clip / norm, 1.0)
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                  + wd * p),
        params, mu, nu)
    return params, mu, nu


def train(params, batches, *, learning_rate, weight_decay=0.0,
          grad_clip_norm=0.0, rows=2, causal=True):
    """One AdamW step on each of `batches` (ids int32 [B, T]) from
    `params`; returns (the loss before each step, the parameters after
    the last). A batch's gradient is the mean over slices of `rows`
    sequences, so that float32 activations of the whole batch are never
    held at once."""
    params = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, ids in enumerate(batches, 1):
        if len(ids) % rows:
            raise ValueError(f"{rows} rows do not divide a batch of {len(ids)}")
        parts = [_loss_and_grad(params, ids[i:i + rows], causal=causal)
                 for i in range(0, len(ids), rows)]
        losses.append(float(sum(x for x, _ in parts)) / len(parts))
        grads = jax.tree.map(lambda *g: sum(g) / len(g), *[g for _, g in parts])
        params, mu, nu = _adamw(params, mu, nu, grads, float(t),
                                learning_rate, weight_decay, grad_clip_norm)
    return losses, params


# ------------------------------------------------------------ comparisons


def logits_error(got, want) -> float:
    """RMS of the difference over the standard deviation of the
    reference's logits: 0 is equal, about 1.4 is unrelated."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))


def update_cosines(start, got, want) -> dict[str, float]:
    """For every module of the model (`block_3/attn`, `block_3/fc1`,
    `lm_head/kernel`, ...: the first two names of a leaf's path), the
    cosine between the change the system made to its parameters and the
    change the reference made: 1 is the same direction, 0 is unrelated,
    and a module the system left where it was reads 0. Not leaf by
    leaf: the key projection's bias has no gradient at all (a softmax
    does not see what is added to every score of a row), so what Adam
    makes of its rounding noise agrees with nothing."""
    sums: dict[str, list] = {}
    leaves = zip(*(jax.tree_util.tree_leaves_with_path(t)
                   for t in (start, got, want)))
    for (path, s), (_, g), (_, w) in leaves:
        s = jnp.asarray(s, jnp.float32)
        a, b = jnp.asarray(g, jnp.float32) - s, jnp.asarray(w, jnp.float32) - s
        acc = sums.setdefault("/".join(str(k.key) for k in path[:2]), [0, 0, 0])
        for i, x in enumerate((jnp.vdot(a, b), jnp.vdot(a, a), jnp.vdot(b, b))):
            acc[i] += x
    return {name: float(ab / jnp.maximum(jnp.sqrt(aa * bb), 1e-30))
            for name, (ab, aa, bb) in sums.items()}
