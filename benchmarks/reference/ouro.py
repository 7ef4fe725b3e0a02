"""Plain reference of the served Ouro decoder (ByteDance Ouro-2.6B, a
looped language model): the equations of ISSUE 34 in `jax.numpy`,
float32, `highest` matmul precision. One full forward over whole
sequences, Python loops over steps and layers: no cache, no kernels, no
`lax` loop. Independent of `hyperion_tpu.models.ouro` but for the names
of the weights it is handed. With `h` the stream `[T, d]`:

  h = embed(ids)
  for t in 0..steps-1:
      for l in 0..layers-1:
          h1 = h  + RMSNorm_2(attn_l(RMSNorm_1(h)))      sandwich block
          h  = h1 + RMSNorm_4(mlp_l(RMSNorm_3(h1)))      mlp = (silu(xG) * xU) D
      h   = RMSNorm_f(h)          the one final norm, at the end of EVERY step
      g_t = sigmoid(h w_gate + b_gate)
  logits = h W_head               (of the last step's normed stream)
  p_t = g_t prod_{s<t} (1 - g_s), the last step taking the rest

The same layers' weights serve every step. Attention: q, k, v, o
without bias, rotary positions (half-split pairing) on q and k at the
token's position in every step, causal softmax; the heads are plain
multi-head in the published model (the repeat below is for a grouped
test size only). The keys and values of a layer in step `t` are those
computed from step `t`'s stream (`keys_values`): nothing is shared
between steps.

Departures from the published description: none in the equations. At
the published `early_exit_threshold` 1.0 no token leaves before the
last step, so the logits are the last step's for every token; `p` is
returned beside them.

One layer's weights are upcast at a time and the head is applied to the
rows asked for only. `upcast`, `between_steps`, `post_norm` and
`keys_values` are functions of their own so that
`tests/bench_harness/ouro_faults.py` can put the reference wrong in one
way and show that the comparison deciding `correct` says so."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder import rms_norm, rope


def upcast(w):
    return w.astype(jnp.float32)


def between_steps(h, w, eps, last: bool):
    """What ends a step and feeds the next (after the `last`, the
    head): the final norm, whichever step it is."""
    return rms_norm(h, w, eps)


def post_norm(y, w, eps):
    """The sandwich's second slice: the norm on what attention or the
    MLP adds to the stream."""
    return rms_norm(y, w, eps)


def keys_values(k, v, shared):
    """The keys and values a step's queries read, [B, T, H, D]: the
    step's own. (`shared`, the last step's of an earlier pass, is what
    a cache shared between steps would hold: a fault reads it.)"""
    return k, v


@partial(jax.jit, static_argnames=("head_dim", "theta", "eps"))
def layer(h, p, shared, *, head_dim, theta, eps):
    """One sandwich block on h [B, T, d]; returns the stream and the
    keys and values it computed."""
    p = jax.tree.map(upcast, p)
    T = h.shape[1]
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, p["input_norm"]["weight"], eps)
        # the q, k, v kernels are handed [out, in]
        q, k, v = ((u @ p[name]["kernel"].T).reshape(
            *u.shape[:2], -1, head_dim)
            for name in ("q_proj", "k_proj", "v_proj"))
        q, k = rope(q, theta), rope(k, theta)
        own = (k, v)
        k, v = keys_values(k, v, shared)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
        o = o.reshape(*u.shape[:2], -1) @ p["o_proj"]["kernel"]
        h = h + post_norm(o, p["attn_post_norm"]["weight"], eps)
        x = rms_norm(h, p["pre_mlp_norm"]["weight"], eps)
        y = (jax.nn.silu(x @ p["gate_proj"]["kernel"])
             * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]
        return h + post_norm(y, p["mlp_post_norm"]["weight"], eps), own


@partial(jax.jit, static_argnames=("eps", "last"))
def step_end(h, norm_w, gate, *, eps, last):
    """The end of a step: the final norm and the exit gate."""
    with jax.default_matmul_precision("highest"):
        h = between_steps(h, upcast(norm_w), eps, last)
        g = h @ upcast(gate["kernel"]) + upcast(gate["bias"])
        return h, jax.nn.sigmoid(g[..., 0])


@jax.jit
def head(x, out_w):
    with jax.default_matmul_precision("highest"):
        return x @ upcast(out_w)


def settings(m: dict) -> dict:
    """What the equations need of a configuration file (HF key names)."""
    return {"n_layers": m["num_hidden_layers"], "head_dim": m["head_dim"],
            "steps": m["total_ut_steps"], "theta": float(m["rope_theta"]),
            "eps": m["rms_norm_eps"]}


def exit_distribution(gates):
    """[g_0 .. g_last], each [B, T] -> p [B, T, steps]."""
    p, left = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        p.append(g * left)
        left = left * (1.0 - g)
    return jnp.stack(p + [left], -1)


def forward(params: dict, ids, *, n_layers: int, head_dim: int, steps: int,
            theta: float, eps: float, rows: tuple[int, int] | None = None, shared=None):
    """ids int32 [B, T] -> (logits float32 [B, T, vocab], exit
    distribution [B, T, steps], the last step's keys and values by
    layer); with `rows = (first, count)` the logits of those positions
    only."""
    loop = params["loop"]
    # the weights come stacked, a run of layers a leaf (`layers_<g>`):
    # layer i is row `i % n` of run `i // n`
    n = jax.tree.leaves(loop["layers_0"])[0].shape[0]
    h = upcast(params["embed_tokens"]["embedding"][ids])
    gates, last = [], None
    for t in range(steps):
        last = []
        for i in range(n_layers):
            h, kv = layer(h, jax.tree.map(lambda w: w[i % n],
                                          loop[f"layers_{i // n}"]),
                          None if shared is None else shared[i],
                          head_dim=head_dim, theta=theta, eps=eps)
            last.append(kv)
        h, g = step_end(h, loop["step_norm"]["weight"], loop["exit_gate"],
                        eps=eps, last=t == steps - 1)
        gates.append(g)
    if rows is not None:
        h = h[:, rows[0]:rows[0] + rows[1]]
    return head(h, params["lm_head"]["kernel"]), exit_distribution(gates), \
        last


def logits(params: dict, ids, **kw):
    """The served logits: the last step's."""
    return forward(params, ids, **kw)[0]
