"""Arcee `afmoe` (Trinity) decoder — the served expert model, in-tree.

Source: `huggingface.co/arcee-ai/Trinity-Large-Preview` `config.json`
(`model_type: afmoe`); what its keys do not carry follows Hugging
Face's `modeling_afmoe.py`. The block differs from `models/llama.py`'s
in norm order, gate and feed-forward, so it is a file of its own; it
shares `RMSNorm`, `apply_rope`, the paged write, the two paged reads
and the choice between them with it. With `x` the residual stream
`[T, d]`:

  * Model: `h = embed(ids) * sqrt(d)` (`mup_enabled`); the layers;
    `logits = lm_head(RMSNorm(h))`, untied.
  * Layer, four RMSNorms: `h = h + post_attn_norm(attn(input_norm(h)))`;
    `h = h + post_mlp_norm(mlp(pre_mlp_norm(h)))`.
  * Attention: q, k, v without bias; RMSNorm over each head's
    `head_dim` on q and k; rotary positions (half-split pairing) on
    `sliding_attention` layers ONLY, none on `full_attention` layers;
    causal softmax, and on a sliding layer a query at p sees keys
    `p - sliding_window < j <= p`; `out = o_proj(a * sigmoid(gate_proj(u)))`
    with `u` the layer's normed input.
  * Feed-forward: SwiGLU of width `ff_dim` on the `n_dense_layers`
    leading layers; on the others `shared(x) + sum_k w_k expert_k(x)`
    over a sigmoid router with a selection bias (`ops/moe.py`
    `dropless_moe`). The auxiliary loss is training's and is left out.

**The share.** `n_experts` is the router's width; `experts_held =
(first, count)` says which experts this chip holds. A pick that lands
elsewhere adds nothing here, the normalisation is over all picks, and
that partial result goes on to the next layer: one chip's part of an
expert-parallel deployment, without its exchange. Nothing stands in
for the absent chips.

**Serving.** The call surface is the engine's: `apply(variables, ids,
cache=, cache_index=, block_tables=)` with `cache` from
`llama.init_paged_cache(cfg, {kind: blocks}, bs)` and `block_tables`
`{kind: [B, MB]}` (`cfg.layer_kinds`: `full`, or `window` with its
size). A call reads its kind's pool as `llama.select_paged_attn_impl`
says for its shape and the backend: the decode tick on a TPU in place,
through the paged-attention kernel with the kind's table and window;
chunks and prefills there through the gather and the tiled kernel over
the view; every other backend through the gather. The
expert layers' grouped products take the form
`ops.moe.select_grouped_impl` names for the call's rows, the held
experts and their matrices' bytes (the grouped-matmul kernel or
`ragged_dot`; no knob here). Without a cache the call is one full
forward (what the tests hold against the reference).

Device scopes: `layer_*/{full,window}/attn/{qkv_proj, qk_norm, rope,
kv_write, kv_read, attention, gate, o_proj}`, `layer_*/mlp`,
`layer_*/moe/{router, dispatch, experts, combine, shared}`. A decode
tick that asks for them (`mutable=["tick_stats"]`) gets each expert
layer's `expert_load` [B, count]: which held experts each row picked.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from hyperion_tpu.models.llama import (
    RMSNorm,
    _grouped_cache_attention,
    apply_rope,
    paged_kv_write,
    paged_read,
    select_paged_attn_impl,
)
from hyperion_tpu.ops.moe import dropless_moe

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    d_model: int = 3072
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    ff_dim: int = 12288               # the leading dense layers' SwiGLU
    moe_ff_dim: int = 3072            # every expert's, and the shared one's
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    n_dense_layers: int = 0           # leading layers with a dense MLP
    n_experts: int = 256              # the router's width
    experts_held: tuple[int, int] = (0, 256)   # (first, count) held here
    top_k: int = 4
    n_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    sliding_window: int = 4096
    max_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    mup_enabled: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is not a run of the "
                f"router's {self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layer_kinds(self) -> tuple[tuple[str, int], ...]:
        """`(kind, window)` per layer, what the serving cache is built
        from: a sliding layer keeps a window of positions, a full layer
        every position."""
        return tuple(("window", self.sliding_window) if t == SLIDING
                     else ("full", 0) for t in self.layer_types)

    @property
    def expert_step(self) -> dict:
        """The static shape of an expert layer's `grouped_experts` call
        but for its rows (`top_k` a token, held here or not): what
        `ops.moe.select_grouped_impl` is asked with, here and by the
        engine's counters."""
        return {"layers": self.n_layers - self.n_dense_layers,
                "groups": self.experts_held[1], "top_k": self.top_k,
                "k": self.d_model, "n": self.moe_ff_dim,
                "itemsize": self.compute_dtype.itemsize}


def afmoe_tiny_config(**kw) -> AfmoeConfig:
    """Test-sized, with every mechanism present: one dense layer, then
    a whole period of expert layers; window 8."""
    base = dict(
        vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        ff_dim=64, moe_ff_dim=16,
        layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
        n_dense_layers=1, n_experts=8, experts_held=(0, 8), top_k=2,
        sliding_window=8, max_len=64, dtype="float32",
    )
    base.update(kw)
    return AfmoeConfig(**base)


def device_rope_table(head_dim: int, max_len: int, theta: float) -> jax.Array:
    """`llama.rope_frequencies`' [max_len, head_dim/2, 2] cos/sin table,
    computed in the program from an iota instead of handed to it as a
    constant. At 12288 positions the constant is 6.3 MB and the compiler
    keeps a copy for every layer that slices it: ten executables of
    22 MB each, more than the chip tool's 192 MiB compile cache holds,
    so that no run ever found its programs there (PERF.md, PR 26). The
    table costs one pass of `max_len x head_dim / 2` sines a call."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim)
    ang = jax.lax.iota(jnp.float32, max_len)[:, None] * jnp.asarray(inv)[None]
    return jnp.stack([jnp.cos(ang), jnp.sin(ang)], -1)


def _dense(c: AfmoeConfig, features, name, axis=-1):
    return nn.DenseGeneral(
        features=features, axis=axis, use_bias=False, dtype=c.compute_dtype,
        kernel_init=nn.initializers.normal(0.02), name=name)


class AfmoeAttention(nn.Module):
    cfg: AfmoeConfig
    kind: tuple[str, int]

    @nn.compact
    def __call__(self, u, rope_table, cache=None, cache_index=None,
                 block_tables=None):
        c = self.cfg
        name, window = self.kind
        H, Hkv, D = c.n_heads, c.n_kv_heads, c.head_dim
        B, T = u.shape[0], u.shape[1]
        with jax.named_scope("qkv_proj"):
            q = _dense(c, (H, D), "q_proj")(u)
            k = _dense(c, (Hkv, D), "k_proj")(u)
            v = _dense(c, (Hkv, D), "v_proj")(u)
        with jax.named_scope("qk_norm"):
            q = RMSNorm(c.norm_eps, c.compute_dtype, name="q_norm")(q)
            k = RMSNorm(c.norm_eps, c.compute_dtype, name="k_norm")(k)
        offset = 0 if cache is None else cache_index
        if name == "window":
            with jax.named_scope("rope"):
                q = apply_rope(q, rope_table, offset)
                k = apply_rope(k, rope_table, offset)
        if cache is None:
            with jax.named_scope("attention"):
                pos = jnp.arange(T)
                mask = pos[None, :] <= pos[:, None]
                if window:
                    mask &= pos[None, :] > pos[:, None] - window
            a, new_cache = _grouped_cache_attention(
                q, k, v, mask, H // Hkv), None
        elif block_tables is None:
            raise ValueError("models/afmoe.py serves through the paged "
                             "cache only: pass block_tables by kind")
        else:
            idx = jnp.asarray(cache_index, jnp.int32)
            base = idx if idx.ndim == 1 else jnp.full((B,), idx, jnp.int32)
            table = block_tables[name]
            ck, cv = paged_kv_write(cache, k, v, table, base)
            # the kind's pool through the kind's table: in place for a
            # few-row window on a TPU (the decode tick), gathered and
            # tiled for a chunk and a prefill there, gathered for the
            # narrowest buckets and on every other backend
            impl = select_paged_attn_impl(
                T, H // Hkv, jax.default_backend())
            a = paged_read(impl, q, ck, cv, table, base, window)
            new_cache = {"k": ck, "v": cv}
        with jax.named_scope("gate"):
            a = a * jax.nn.sigmoid(_dense(c, (H, D), "gate_proj")(u))
        with jax.named_scope("o_proj"):
            out = _dense(c, c.d_model, "o_proj", axis=(-2, -1))(a)
        return out, new_cache


class SwiGLU(nn.Module):
    cfg: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        gate = _dense(c, self.width, "gate_proj")(x)
        up = _dense(c, self.width, "up_proj")(x)
        return _dense(c, c.d_model, "down_proj")(nn.silu(gate) * up)


class AfmoeMoE(nn.Module):
    """Shared expert + this chip's share of the routed experts."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, d = x.shape
        count, f = c.experts_held[1], c.moe_ff_dim
        init = nn.initializers.normal(0.02)
        params = {
            "router": self.param("router", init, (d, c.n_experts),
                                 c.compute_dtype),
            "expert_bias": self.param(
                "expert_bias", nn.initializers.zeros, (c.n_experts,),
                jnp.float32),
            "gate": self.param("experts_gate", init, (count, d, f),
                               c.compute_dtype),
            "up": self.param("experts_up", init, (count, d, f),
                             c.compute_dtype),
            "down": self.param("experts_down", init, (count, f, d),
                               c.compute_dtype),
        }
        routed, load = dropless_moe(
            x.reshape(B * T, d), params, held=c.experts_held, top_k=c.top_k,
            route_norm=c.route_norm, route_scale=c.route_scale)
        self.sow("tick_stats", "expert_load",
                 load.reshape(B, T, count).sum(axis=1))
        with jax.named_scope("shared"):
            shared = SwiGLU(c, f * c.n_shared_experts, name="shared")(x)
        with jax.named_scope("combine"):
            return shared + routed.reshape(B, T, d)


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x, rope_table, cache=None, cache_index=None,
                 block_tables=None):
        c = self.cfg
        kind = c.layer_kinds[self.index]

        def norm(name):
            return RMSNorm(c.norm_eps, c.compute_dtype, name=name)

        # the parent scope says the layer's kind: device time of the
        # cache paths reads by kind (obs/xprof.py)
        with jax.named_scope(kind[0]):
            a, cache = AfmoeAttention(c, kind, name="attn")(
                norm("input_norm")(x), rope_table, cache, cache_index,
                block_tables)
        x = x + norm("post_attn_norm")(a)
        h = norm("pre_mlp_norm")(x)
        if self.index < c.n_dense_layers:
            m = SwiGLU(c, c.ff_dim, name="mlp")(h)
        else:
            m = AfmoeMoE(c, name="moe")(h)
        return x + norm("post_mlp_norm")(m), cache


class Afmoe(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, cache_index=None,
                 block_tables=None):
        """input_ids int32 [B, T] → logits fp32 [B, T, vocab], or with
        `cache` (paged, by kind) → (logits, updated cache)."""
        c = self.cfg
        x = nn.Embed(
            c.vocab_size, c.d_model, dtype=c.compute_dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed_tokens",
        )(input_ids)
        if c.mup_enabled:
            x = x * jnp.asarray(np.sqrt(c.d_model), x.dtype)
        with jax.named_scope("rope_table"):
            rope = device_rope_table(c.head_dim, c.max_len, c.rope_theta)
        new_cache = []
        for i in range(c.n_layers):
            x, layer_cache = AfmoeBlock(c, i, name=f"layer_{i}")(
                x, rope, None if cache is None else cache[i], cache_index,
                block_tables)
            new_cache.append(layer_cache)
        x = RMSNorm(c.norm_eps, c.compute_dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            logits = _dense(c, c.vocab_size, "lm_head")(x)
            logits = logits.astype(jnp.float32)
        return logits if cache is None else (logits, new_cache)

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: int | None = None):
        ids = jnp.zeros((batch, seq or 8), jnp.int32)
        return self.init(rng, ids)["params"]
