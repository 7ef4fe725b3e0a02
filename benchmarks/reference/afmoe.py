"""Plain reference of the served `afmoe` decoder (Arcee Trinity): the
layer equations of ISSUE 26 in `jax.numpy`, float32, `highest` matmul
precision. One full forward over whole sequences: no cache, no kernels,
no sorting of tokens, no grouped products. Independent of
`hyperion_tpu.models.afmoe` but for the names of the weights it is
handed.

  h = embed(ids) * sqrt(d)
  h = h + post_attn_norm(attn(input_norm(h)))
  h = h + post_mlp_norm(mlp(pre_mlp_norm(h)))         four RMSNorms a layer
  logits = lm_head(RMSNorm(h))

Attention: q, k, v; RMSNorm over each head's width on q and k; rotary
positions on sliding layers only; causal, and on a sliding layer a
query at p sees keys `p - window < j <= p`; the result times
`sigmoid(gate_proj(u))`, `u` the layer's normed input, then `o_proj`.
Feed-forward: SwiGLU on the leading dense layers; on the others
`shared(x) + sum_k w_k expert_k(x)` with `s = sigmoid(x W_r)`, the
`top_k` largest of `s + expert_bias` picked, `w = s[picked]` normalised
over all picks and scaled. `held = (first, count)` is the share: only
those experts' weights are here, and a pick that lands elsewhere adds
nothing, as in the program.

So that 8192 positions fit on the chip beside the bf16 weights: one
layer's weights are upcast at a time, an expert layer's one expert at a
time, and attention runs in blocks of queries. `upcast`, `qk_norm`,
`output_gate` and `shared_expert` are functions of their own so that
`tests/bench_harness/afmoe_faults.py` can put the reference wrong in one
way and show that the comparison deciding `correct` says so."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder import rms_norm, rope

Q_BLOCK = 512


def upcast(w):
    return w.astype(jnp.float32)


def _f32(tree):
    return jax.tree.map(upcast, tree)


def qk_norm(q, k, p, eps: float):
    """RMSNorm over each head's width."""
    return (rms_norm(q, p["q_norm"]["weight"], eps),
            rms_norm(k, p["k_norm"]["weight"], eps))


def output_gate(a, u, kernel):
    """The heads' results times a sigmoid of the layer's normed input."""
    return a * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", u, kernel))


def attention(u, p, *, window: int, rotary: bool, theta: float, eps: float):
    """u [B, T, d], the layer's normed input; `window` 0 = full."""
    p = _f32(p)
    T = u.shape[1]
    q = jnp.einsum("btd,dhk->bthk", u, p["q_proj"]["kernel"])
    k = jnp.einsum("btd,dhk->bthk", u, p["k_proj"]["kernel"])
    v = jnp.einsum("btd,dhk->bthk", u, p["v_proj"]["kernel"])
    q, k = qk_norm(q, k, p, eps)
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
    a = output_gate(a, u, p["gate_proj"]["kernel"])
    return jnp.einsum("bqhk,hkd->bqd", a, p["o_proj"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_weights(x, router, bias, *, n_held_from: int, n_held: int,
                   top_k: int, route_norm: bool, route_scale: float):
    """x [N, d] -> [N, n_held]: the weight each held expert's output
    gets for each token, 0 where the token did not pick it."""
    s = jax.nn.sigmoid(x @ router)                        # [N, E]
    _, picked = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, picked, -1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * route_scale
    full = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], picked].add(w)
    return full[:, n_held_from:n_held_from + n_held]


def shared_expert(flat, p):
    sh = _f32(p)
    return swiglu(flat, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                  sh["down_proj"]["kernel"])


def moe(x, p, *, held, top_k: int, route_norm: bool, route_scale: float):
    """x [B, T, d]: shared expert + the held experts' part of the routed
    sum, every held expert applied to every token and weighted (0 where
    not picked): the plain form of what the program computes grouped."""
    B, T, d = x.shape
    flat = x.reshape(B * T, d)
    w = router_weights(
        flat, upcast(p["router"]),
        p["expert_bias"].astype(jnp.float32), n_held_from=held[0],
        n_held=held[1], top_k=top_k, route_norm=route_norm,
        route_scale=route_scale)

    def one(acc, e):
        gate, up, down, we = e
        y = swiglu(flat, upcast(gate), upcast(up), upcast(down))
        return acc + we[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(flat),
        (p["experts_gate"], p["experts_up"], p["experts_down"], w.T))
    return (shared_expert(flat, p["shared"]) + routed).reshape(B, T, d)


@partial(jax.jit, static_argnames=(
    "window", "rotary", "theta", "eps", "held", "top_k", "route_norm",
    "route_scale"))
def layer(x, p, *, window, rotary, theta, eps, held, top_k, route_norm,
          route_scale):
    """One block on x [B, T, d]; the layer has a `mlp` (dense) or a
    `moe` entry, and that decides its feed-forward."""
    def norm(name, y):
        return rms_norm(y, p[name]["weight"].astype(jnp.float32), eps)

    with jax.default_matmul_precision("highest"):
        a = attention(norm("input_norm", x), p["attn"], window=window,
                      rotary=rotary, theta=theta, eps=eps)
        x = x + norm("post_attn_norm", a)
        h = norm("pre_mlp_norm", x)
        if "mlp" in p:
            m = _f32(p["mlp"])
            f = swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"])
        else:
            f = moe(h, p["moe"], held=held, top_k=top_k,
                    route_norm=route_norm, route_scale=route_scale)
        return x + norm("post_mlp_norm", f)


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, out_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_w.astype(jnp.float32), eps) @ upcast(out_w)


def settings(m: dict) -> dict:
    """What the equations need of a configuration file (HF key names;
    `layers_kept` indexes the published `layer_types`)."""
    return {
        "layer_types": tuple(m["layer_types"][i] for i in m["layers_kept"]),
        "window": m["sliding_window"], "theta": float(m["rope_theta"]),
        "eps": m["rms_norm_eps"], "held": tuple(m["experts_held"]),
        "top_k": m["num_experts_per_tok"], "route_norm": m["route_norm"],
        "route_scale": m["route_scale"], "mup": m["mup_enabled"],
    }


def logits(params: dict, ids, *, layer_types, window: int, theta: float,
           eps: float, held, top_k: int, route_norm: bool,
           route_scale: float, mup: bool):
    """ids int32 [B, T] -> float32 [B, T, vocab]."""
    x = upcast(params["embed_tokens"]["embedding"][ids])
    if mup:
        x = x * jnp.sqrt(jnp.float32(x.shape[-1]))
    for i, kind in enumerate(layer_types):
        sliding = kind == "sliding_attention"
        x = layer(x, params[f"layer_{i}"], window=window if sliding else 0,
                  rotary=sliding, theta=theta, eps=eps, held=tuple(held),
                  top_k=top_k, route_norm=route_norm,
                  route_scale=route_scale)
    return head(x, params["final_norm"]["weight"],
                params["lm_head"]["kernel"], eps=eps)
