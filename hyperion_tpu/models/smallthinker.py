"""PowerInfer SmallThinker decoder (21B-A3B) — the second served expert
model, in-tree.

Source: `huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct`
`config.json` (`smallthinker_21b_instruct`); what its keys do not carry
is listed under `assumed` in the benchmark's configuration file. The
block is neither `models/llama.py`'s nor `models/afmoe.py`'s: its
router reads the residual stream as it ENTERS the layer, before the
input norm and before attention, and the picks it makes there are
handed to the experts after attention. It shares `RMSNorm`,
`apply_rope`, the paged write, the two paged reads and the choice
between them with `llama`, the rotary table computed in the program
with `afmoe`, and the grouped expert step of `ops/moe.py` with
`afmoe`. With `h` the residual stream `[T, d]` entering a layer:

  * Model: `h = embed(ids)` (unscaled); the layers;
    `logits = lm_head(RMSNorm(h))`, untied.
  * Layer, two RMSNorms: `r = h W_r` (float32 logits, from the layer's
    INPUT); `h1 = h + attn(input_norm(h))`; `picked = top_k(r)`,
    `w = softmax(r[picked])`; `h2 = h1 + sum_k w_k
    expert_{picked_k}(post_attn_norm(h1))` with `expert(x) =
    (relu(x G) * (x U)) D` (`ops/moe.py` `softmax_topk_route`, then
    `grouped_experts`). No shared expert, no dense layer anywhere.
  * Attention: q, k, v without bias, no QK-norm, no output gate;
    rotary positions (half-split pairing) where `rope_layout` says 1,
    none where it says 0; causal softmax, and where
    `sliding_window_layout` says 1 a query at p sees keys
    `p - sliding_window < j <= p`. In the published model the two
    layouts are equal: full layers carry no positions (NoPE).

Every expert of a layer is held where the layer is: no deployment of
this model here shares a layer's experts among chips (`grouped_experts`
takes a share, `held`, for `afmoe`; this model passes the whole run).

**Serving.** The call surface is the engine's: `apply(variables, ids,
cache=, cache_index=, block_tables=)` with `cache` from
`llama.init_paged_cache(cfg, {kind: blocks}, bs)` and `block_tables`
`{kind: [B, MB]}` (`cfg.layer_kinds`). A call reads its kind's pool as
`llama.select_paged_attn_impl` says for its shape and the backend (7
query heads a KV head: the decode tick on a TPU takes the kernel, a
chunk and a prefill there the gather and the tiled kernel over it),
and multiplies its sorted rows with the experts' matrices as
`ops.moe.select_grouped_impl` says for its shape and the backend (the
grouped-matmul kernel for the tick's few rows an expert on a TPU,
`ragged_dot` otherwise).
Without a cache the call is one full forward.

Device scopes: `layer_*/router`, `layer_*/{full,window}/attn/{qkv_proj,
rope, kv_write, kv_read, attention, o_proj}`, `layer_*/moe/{dispatch,
experts, combine}`. A decode tick that asks for them
(`mutable=["tick_stats"]`) gets each layer's `expert_load` [B, count],
as from `afmoe`.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from hyperion_tpu.models.afmoe import device_rope_table
from hyperion_tpu.models.llama import (
    RMSNorm,
    _grouped_cache_attention,
    apply_rope,
    paged_kv_write,
    paged_read,
    select_paged_attn_impl,
)
from hyperion_tpu.ops.moe import grouped_experts, softmax_topk_route

_PERIOD = (0, 1, 1, 1)      # the published layout: full, then 3 sliding


@dataclasses.dataclass(frozen=True)
class SmallthinkerConfig:
    vocab_size: int = 151936
    d_model: int = 2560
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_ff_dim: int = 768                   # moe_ffn_hidden_size
    n_experts: int = 64                     # moe_num_primary_experts
    top_k: int = 6                          # moe_num_active_primary_experts
    # per layer, 1 = sliding window (0 = every position), 1 = rotary
    # positions (0 = none)
    sliding_window_layout: tuple[int, ...] = _PERIOD * 13
    rope_layout: tuple[int, ...] = _PERIOD * 13
    sliding_window: int = 4096              # sliding_window_size
    max_len: int = 16384
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.rope_layout) != len(self.sliding_window_layout):
            raise ValueError(
                f"rope_layout has {len(self.rope_layout)} layers, "
                f"sliding_window_layout {len(self.sliding_window_layout)}")

    @property
    def n_layers(self) -> int:
        return len(self.sliding_window_layout)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layer_kinds(self) -> tuple[tuple[str, int], ...]:
        """`(kind, window)` per layer, what the serving cache is built
        from: a sliding layer keeps a window of positions, a full layer
        every position."""
        return tuple(("window", self.sliding_window) if s else ("full", 0)
                     for s in self.sliding_window_layout)

    @property
    def expert_step(self) -> dict:
        """The static shape of a layer's `grouped_experts` call but for
        its rows (`top_k` a token): what `ops.moe.select_grouped_impl`
        is asked with, here and by the engine's counters."""
        return {"layers": self.n_layers, "groups": self.n_experts,
                "top_k": self.top_k, "k": self.d_model,
                "n": self.moe_ff_dim,
                "itemsize": self.compute_dtype.itemsize}


def smallthinker_tiny_config(**kw) -> SmallthinkerConfig:
    """Test-sized: two whole periods (full + 3 sliding, twice), 7 query
    heads a KV head, window 8."""
    base = dict(
        vocab_size=96, d_model=32, n_heads=14, n_kv_heads=2, head_dim=8,
        moe_ff_dim=16, n_experts=16, top_k=6,
        sliding_window_layout=_PERIOD * 2, rope_layout=_PERIOD * 2,
        sliding_window=8, max_len=64, dtype="float32",
    )
    base.update(kw)
    return SmallthinkerConfig(**base)


def _dense(c: SmallthinkerConfig, features, name, axis=-1):
    return nn.DenseGeneral(
        features=features, axis=axis, use_bias=False, dtype=c.compute_dtype,
        kernel_init=nn.initializers.normal(0.02), name=name)


class SmallthinkerRouter(nn.Module):
    """Which experts each token of the layer's INPUT picks, and their
    weights: computed at the layer's entry, used after attention."""
    cfg: SmallthinkerConfig

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (c.d_model, c.n_experts), c.compute_dtype)
        return softmax_topk_route(
            h.reshape(-1, c.d_model), kernel, top_k=c.top_k)


class SmallthinkerAttention(nn.Module):
    cfg: SmallthinkerConfig
    kind: tuple[str, int]
    rotary: bool

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
        if self.rotary:
            offset = 0 if cache is None else cache_index
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
            raise ValueError("models/smallthinker.py serves through the "
                             "paged cache only: pass block_tables by kind")
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
        with jax.named_scope("o_proj"):
            out = _dense(c, c.d_model, "o_proj", axis=(-2, -1))(a)
        return out, new_cache


class SmallthinkerExperts(nn.Module):
    """The layer's ReLU-gated experts, on picks made at the layer's
    entry."""
    cfg: SmallthinkerConfig

    @nn.compact
    def __call__(self, x, picked, w):
        c = self.cfg
        B, T, d = x.shape
        count, f = c.n_experts, c.moe_ff_dim
        init = nn.initializers.normal(0.02)
        params = {
            "gate": self.param("experts_gate", init, (count, d, f),
                               c.compute_dtype),
            "up": self.param("experts_up", init, (count, d, f),
                             c.compute_dtype),
            "down": self.param("experts_down", init, (count, f, d),
                               c.compute_dtype),
        }
        y, load = grouped_experts(
            x.reshape(B * T, d), picked, w, params, held=(0, count),
            act=jax.nn.relu)
        self.sow("tick_stats", "expert_load",
                 load.reshape(B, T, count).sum(axis=1))
        return y.reshape(B, T, d)


class SmallthinkerBlock(nn.Module):
    cfg: SmallthinkerConfig
    index: int

    @nn.compact
    def __call__(self, h, rope_table, cache=None, cache_index=None,
                 block_tables=None):
        c = self.cfg
        kind = c.layer_kinds[self.index]

        def norm(name):
            return RMSNorm(c.norm_eps, c.compute_dtype, name=name)

        # the router reads the stream as it enters the layer: before the
        # input norm, before attention
        picked, w = SmallthinkerRouter(c, name="router")(h)
        # the parent scope says the layer's kind: device time of the
        # cache paths reads by kind (obs/xprof.py)
        with jax.named_scope(kind[0]):
            a, cache = SmallthinkerAttention(
                c, kind, bool(c.rope_layout[self.index]), name="attn")(
                norm("input_norm")(h), rope_table, cache, cache_index,
                block_tables)
        h = h + a
        m = SmallthinkerExperts(c, name="moe")(
            norm("post_attn_norm")(h), picked, w)
        return h + m, cache


class Smallthinker(nn.Module):
    cfg: SmallthinkerConfig

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
        with jax.named_scope("rope_table"):
            rope = device_rope_table(c.head_dim, c.max_len, c.rope_theta)
        new_cache = []
        for i in range(c.n_layers):
            x, layer_cache = SmallthinkerBlock(c, i, name=f"layer_{i}")(
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
