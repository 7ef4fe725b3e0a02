"""Plain reference of the served SmallThinker decoder (PowerInfer
SmallThinker-21BA3B-Instruct): the layer equations of ISSUE 31 in
`jax.numpy`, float32, `highest` matmul precision. One full forward over
whole sequences: no cache, no kernels, no sorting of tokens, no grouped
products. Independent of `hyperion_tpu.models.smallthinker` but for the
names of the weights it is handed. With `h` the stream entering a layer:

  r  = h W_r                          router logits, from the layer's INPUT
  u  = RMSNorm_in(h)
  h1 = h + attn(u) W_o                no bias, no QK-norm, no gate
  x  = RMSNorm_post(h1)
  picked = top_k(r);  w = softmax(r[picked])
  h2 = h1 + sum_k w_k (relu(x G_k) * (x U_k)) D_k
  model: h = embed(ids) (unscaled); logits = RMSNorm_f(h) W_head

Attention: rotary positions (half-split pairing) on the layers whose
`rope_layout` is 1 and none on the others; causal, and on a layer whose
`sliding_window_layout` is 1 a query at p sees keys `p - window < j <=
p`.

So that 8192 positions fit on the chip beside the bf16 weights: one
layer's weights are upcast at a time, an expert layer's one expert at a
time, attention runs in blocks of queries, and the head (151936 wide)
is applied to the rows asked for only. `router_input`, `gate_act`,
`route_weights` and `upcast` are functions of their own so that
`tests/bench_harness/smallthinker_faults.py` can put the reference
wrong in one way and show that the comparison deciding `correct` says
so."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder import rms_norm, rope

Q_BLOCK = 512


def upcast(w):
    return w.astype(jnp.float32)


def router_input(h, u, x):
    """What the router reads, of the stream entering the layer (`h`),
    its input-normed form (`u`) and the post-attention normed state the
    experts read (`x`): the layer's input."""
    return h


def gate_act(g):
    return jax.nn.relu(g)


def route_weights(r, top_k: int):
    """Router logits r [N, E] -> [N, E]: the weight each expert's
    output gets for each token, a softmax over the `top_k` picked
    logits, 0 where the token did not pick it."""
    top, picked = jax.lax.top_k(r, top_k)
    return jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], picked].add(
            jax.nn.softmax(top, -1))


def attention(u, p, *, window: int, rotary: bool, theta: float):
    """u [B, T, d], the layer's normed input; `window` 0 = full."""
    p = jax.tree.map(upcast, p)
    T = u.shape[1]
    q = jnp.einsum("btd,dhk->bthk", u, p["q_proj"]["kernel"])
    k = jnp.einsum("btd,dhk->bthk", u, p["k_proj"]["kernel"])
    v = jnp.einsum("btd,dhk->bthk", u, p["v_proj"]["kernel"])
    if rotary:
        q, k = rope(q, theta), rope(k, theta)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    key_pos = jnp.arange(T)[None, :]

    def block(q0, qb):
        """Queries q0.. of one block against every key."""
        s = jnp.einsum("bqhk,bshk->bhqs", qb, k) / jnp.sqrt(q.shape[-1])
        q_pos = (q0 + jnp.arange(qb.shape[1]))[:, None]
        seen = key_pos <= q_pos
        if window:
            seen &= key_pos > q_pos - window
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)

    if T > Q_BLOCK and T % Q_BLOCK == 0:
        # one block of scores at a time: [B, H, Q_BLOCK, T] float32
        n = T // Q_BLOCK
        qs = q.reshape(q.shape[0], n, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)
        a = jax.lax.map(lambda e: block(e[0], e[1]),
                        (jnp.arange(n) * Q_BLOCK, qs))
        a = a.swapaxes(0, 1).reshape(q.shape)
    else:
        a = block(0, q)
    return jnp.einsum("bqhk,hkd->bqd", a, p["o_proj"]["kernel"])


def experts(x, r, p, *, top_k: int):
    """x [N, d] and the router's logits r [N, E]: the routed sum, every
    expert applied to every token and weighted (0 where not picked):
    the plain form of what the program computes grouped."""
    w = route_weights(r, top_k)

    def one(acc, e):
        gate, up, down, we = e
        y = (gate_act(x @ upcast(gate)) * (x @ upcast(up))) @ upcast(down)
        return acc + we[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_gate"], p["experts_up"], p["experts_down"], w.T))
    return routed


@partial(jax.jit, static_argnames=(
    "window", "rotary", "theta", "eps", "top_k"))
def layer(h, p, *, window, rotary, theta, eps, top_k):
    """One block on h [B, T, d]."""
    def norm(name, y):
        return rms_norm(y, p[name]["weight"].astype(jnp.float32), eps)

    B, T, d = h.shape
    with jax.default_matmul_precision("highest"):
        u = norm("input_norm", h)
        h1 = h + attention(u, p["attn"], window=window, rotary=rotary,
                           theta=theta)
        x = norm("post_attn_norm", h1)
        r = router_input(h, u, x).reshape(B * T, d) \
            @ upcast(p["router"]["kernel"])
        m = experts(x.reshape(B * T, d), r, p["moe"], top_k=top_k)
        return h1 + m.reshape(B, T, d)


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, out_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_w.astype(jnp.float32), eps) @ upcast(out_w)


def settings(m: dict) -> dict:
    """What the equations need of a configuration file (HF key names;
    `layers_kept` indexes the published layouts)."""
    kept = m["layers_kept"]
    return {
        "sliding": tuple(m["sliding_window_layout"][i] for i in kept),
        "rotary": tuple(m["rope_layout"][i] for i in kept),
        "window": m["sliding_window_size"], "theta": float(m["rope_theta"]),
        "eps": m["rms_norm_eps"],
        "top_k": m["moe_num_active_primary_experts"],
    }


def logits(params: dict, ids, *, sliding, rotary, window: int, theta: float,
           eps: float, top_k: int, rows: tuple[int, int] | None = None):
    """ids int32 [B, T] -> float32 [B, T, vocab], or with `rows =
    (first, count)` the logits of those positions only, [B, count,
    vocab]: at 151936 ids a row the whole of a long request would not
    fit beside the weights."""
    x = upcast(params["embed_tokens"]["embedding"][ids])
    for i, (slide, rot) in enumerate(zip(sliding, rotary)):
        x = layer(x, params[f"layer_{i}"], window=window if slide else 0,
                  rotary=bool(rot), theta=theta, eps=eps, top_k=top_k)
    if rows is not None:
        x = x[:, rows[0]:rows[0] + rows[1]]
    return head(x, params["final_norm"]["weight"],
                params["lm_head"]["kernel"], eps=eps)
