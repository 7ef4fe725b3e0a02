"""Plain reference of the served Solar Open 2 decoder (Upstage
Solar-Open2-250B, `model_type: solar_open2`): the layer equations
below in `jax.numpy`, float32, `highest` matmul precision. One full
forward over whole sequences: no cache, no kernels, no chunks, no
sorting of tokens, no grouped products. The linear-attention layers run
the recurrence TOKEN BY TOKEN (a `lax.scan` over positions), as the
equations write it. Independent of `hyperion_tpu.models.solar_open2`
but for the names of the weights it is handed. With `h` the stream
entering a layer and `u = RMSNorm_in(h)`:

  KDA layer (every layer outside `gqa_layers`), H heads of K channels:
    q, k, v = SiLU(conv4(u W_q)), SiLU(conv4(u W_k)), SiLU(conv4(u W_v))
        (causal depthwise over the last four positions, no bias)
    q = q / |q| / sqrt(K);  k = k / |k|        per head (eps 1e-6)
    g = -exp(A_log[h]) softplus(u W_f1 W_f2 + dt_bias);  alpha = exp(g)
    beta = 2 sigmoid(u W_b)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;   out = (RMSNorm_head(o) * sigmoid(u W_g1 W_g2)) W_o
  GQA layer: causal softmax over 64 query heads on 8 KV heads, no
    positions, no QK-norm; out = (a * sigmoid(u W_gate)) W_o
  h1 = h + out;   h2 = h1 + shared(x) + sum_k w_k expert_k(x),
    x = RMSNorm_mlp(h1), s = sigmoid(x W_r), picks = top_k(s + bias),
    w = s[picks] normalised over all picks (routed_scaling_factor 1)
  model: h = embed(ids) (unscaled); logits = RMSNorm_f(h) W_head

Departures from the published description, each also in the
configuration file's `assumed` or `reduced`:

  * the router's score function (sigmoid in float32, a selection bias
    that moves picks only), the gate's form (elementwise sigmoid) and the
    absence of QK-norm are not in the published row: assumed;
  * the state is float32 (the published chunked kernels carry it so);
  * `held = (first, count)` is one chip's share of the 320 experts: only
    those experts' weights are here, and a pick that lands elsewhere adds
    nothing, as in the program;
  * 4 of 48 layers, a slice of the vocabulary (the configuration's cut).

So that the cell's contexts fit on the chip beside the bf16 weights:
one layer's weights are upcast at a time, an expert layer's one expert
at a time, and attention runs in blocks of queries. `short_conv`,
`log_decay`, `update_strength` and `positions` are functions of their
own so that a test can put the reference wrong in
one way and show that the comparison deciding `correct` says so."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import moe
from benchmarks.reference.decoder import rms_norm

Q_BLOCK = 512


def upcast(w):
    return w.astype(jnp.float32)


def _f32(tree):
    return jax.tree.map(upcast, tree)


def short_conv(x, w):
    """x [B, T, C], w [taps, C]: the causal depthwise convolution, tap j
    on position t - (taps - 1) + j, positions before 0 zero."""
    taps = w.shape[0]
    xs = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xs[:, j:j + x.shape[1]] * w[j] for j in range(taps))


def log_decay(f, a_log, dt_bias):
    """f [B, T, H, K] → g = -exp(A_log[h]) softplus(f + dt_bias)."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)


def update_strength(b):
    """b [B, T, H] → beta in (0, 2): negative eigenvalues allowed."""
    return 2.0 * jax.nn.sigmoid(b)


def positions(q, k, theta):
    """What the GQA layer does with positions: nothing (NoPE)."""
    return q, k


def recurrence(q, k, v, g, beta):
    """The gated delta rule token by token from S = 0: q, k, v, g
    [B, T, H, K], beta [B, T, H] → o [B, T, H, K]."""
    B, _, H, K = k.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        S = S - bt[..., None, None] * kt[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", kt, S)[..., None, :]
        S = S + bt[..., None, None] * kt[..., None] * vt[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, K), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def kda(u, p, *, eps: float):
    """The KDA layer on u [B, T, d], its normed input."""
    p = _f32(p)
    B, T, _ = u.shape
    H, K = p["A_log"].shape[0], p["o_norm"]["weight"].shape[0]
    proj = [u @ p[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj")]
    w = jnp.split(p["conv"], 3, axis=-1)
    q, k, v = (jax.nn.silu(short_conv(x, wx)).reshape(B, T, H, K)
               for x, wx in zip(proj, w))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / jnp.sqrt(jnp.float32(K))
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = jnp.einsum("btr,rhk->bthk", u @ p["f_a_proj"]["kernel"],
                   p["f_b_proj"]["kernel"])
    g = log_decay(f, p["A_log"], p["dt_bias"].reshape(H, K))
    beta = update_strength(u @ p["b_proj"]["kernel"])
    o = rms_norm(recurrence(q, k, v, g, beta), p["o_norm"]["weight"], eps)
    gate = jnp.einsum("btr,rhk->bthk", u @ p["g_a_proj"]["kernel"],
                      p["g_b_proj"]["kernel"])
    return jnp.einsum("bthk,hkd->btd", o * jax.nn.sigmoid(gate),
                      p["o_proj"]["kernel"])


def attention(u, p, *, theta: float):
    """The gated GQA layer on u [B, T, d], its normed input."""
    p = _f32(p)
    T = u.shape[1]
    q = jnp.einsum("btd,dhk->bthk", u, p["q_proj"]["kernel"])
    k = jnp.einsum("btd,dhk->bthk", u, p["k_proj"]["kernel"])
    v = jnp.einsum("btd,dhk->bthk", u, p["v_proj"]["kernel"])
    q, k = positions(q, k, theta)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    key_pos = jnp.arange(T)[None, :]

    def block(q0, qb):
        """Queries q0.. of one block against every key."""
        s = jnp.einsum("bqhk,bshk->bhqs", qb, k) / jnp.sqrt(q.shape[-1])
        seen = key_pos <= (q0 + jnp.arange(qb.shape[1]))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)

    if T > Q_BLOCK and T % Q_BLOCK == 0:
        n = T // Q_BLOCK
        qs = q.reshape(q.shape[0], n, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)
        a = jax.lax.map(lambda e: block(e[0], e[1]),
                        (jnp.arange(n) * Q_BLOCK, qs))
        a = a.swapaxes(0, 1).reshape(q.shape)
    else:
        a = block(0, q)
    a = a * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", u,
                                      p["gate_proj"]["kernel"]))
    return jnp.einsum("bqhk,hkd->bqd", a, p["o_proj"]["kernel"])


@partial(jax.jit, static_argnames=("kind", "theta", "eps", "held", "top_k"))
def layer(x, p, *, kind: str, theta, eps, held, top_k):
    """One block on x [B, T, d]; `kind` "full" (GQA) or "recurrent"."""
    def norm(name, y):
        return rms_norm(y, upcast(p[name]["weight"]), eps)

    with jax.default_matmul_precision("highest"):
        u = norm("input_norm", x)
        if kind == "full":
            x = x + attention(u, p["attn"], theta=theta)
        else:
            x = x + kda(u, p["recurrent"], eps=eps)
        return x + moe(norm("pre_mlp_norm", x), p["moe"], held=held,
                       top_k=top_k, route_norm=True, route_scale=1.0)


@partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, out_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, upcast(norm_w), eps) @ upcast(out_w)


def settings(m: dict) -> dict:
    """What the equations need of a configuration file (HF key names;
    `layers_kept` indexes the published layers)."""
    return {
        "layer_types": tuple("full" if i in m["gqa_layers"] else "recurrent"
                             for i in m["layers_kept"]),
        "theta": float(m["rope_theta"]), "eps": m["rms_norm_eps"],
        "held": tuple(m["experts_held"]), "top_k": m["num_experts_per_tok"],
    }


def logits(params: dict, ids, *, layer_types, theta: float, eps: float,
           held, top_k: int):
    """ids int32 [B, T] -> float32 [B, T, vocab]."""
    x = upcast(params["embed_tokens"]["embedding"][ids])
    for i, kind in enumerate(layer_types):
        x = layer(x, params[f"layer_{i}"], kind=kind, theta=theta, eps=eps,
                  held=tuple(held), top_k=top_k)
    return head(x, params["final_norm"]["weight"],
                params["lm_head"]["kernel"], eps=eps)
