"""Transformer language models (toy + GPT-2-shaped).

Capability parity targets:
  * `SimpleTransformerLM` — emb 256, 4 heads, 2 encoder layers, GPT-2
    vocab 50257 (`distributed_utils.py:75-88`) → `simple_lm_config()`.
  * the compile-benchmark GPT-2-shaped variant — d_model 768, 4 layers,
    12 heads, ff 3072, GELU (`compilation_optimization.py:57-71`)
    → `gpt2_lm_config()`.

TPU-first design choices (deliberately not a torch translation):
  * pre-LayerNorm blocks (stable in bf16 without warmup tricks; the
    torch default is post-LN),
  * attention in [B, T, H, D] layout via `hyperion_tpu.ops.attention`
    so the seq axis can shard for ring attention,
  * causal masking in-model (the reference shifts inputs/targets but
    its encoder attends bidirectionally — a known quirk of the
    reference's toy; ours is a true causal LM, strictly better),
  * optional `jax.checkpoint` rematerialisation per block — the
    activation-checkpointing analogue (`memory_optimization.ipynb
    cell 3:16-18`) expressed as a compiler policy, not an API wrapper.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from hyperion_tpu.data.text import GPT2_VOCAB_SIZE
from hyperion_tpu.ops.attention import dot_product_attention
from hyperion_tpu.ops.pallas.fused_norm import fused_layernorm


@dataclasses.dataclass(frozen=True)
class TransformerLMConfig:
    vocab_size: int = GPT2_VOCAB_SIZE
    d_model: int = 256
    n_heads: int = 4
    n_layers: int = 2
    ff_dim: int = 1024
    max_len: int = 128
    dropout: float = 0.1
    activation: str = "relu"       # relu | gelu
    attention_impl: str = "xla"    # xla | pallas
    norm_impl: str = "xla"         # xla | pallas (fused_layernorm kernel)
    causal: bool = True            # False → bidirectional encoder blocks
    # rematerialisation: False/"none", True/"full", or a named policy
    # from precision.remat.REMAT_POLICIES ("dots", "dots_no_batch")
    remat: bool | str = False
    dtype: str = "float32"         # compute dtype; params stay fp32
    # "none" | "int8": weight-only int8 inference — dense kernels become
    # int8+scale (precision/quant.py, converted by quantize_lm); biases,
    # norms and embeddings stay float. Inference-only.
    quant: str = "none"

    @property
    def remat_policy(self) -> str:
        from hyperion_tpu.precision.remat import normalize_remat

        return normalize_remat(self.remat)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def simple_lm_config(**kw) -> TransformerLMConfig:
    return TransformerLMConfig(**kw)


def gpt2_lm_config(**kw) -> TransformerLMConfig:
    base = dict(d_model=768, n_heads=12, n_layers=4, ff_dim=3072, activation="gelu")
    base.update(kw)
    return TransformerLMConfig(**base)


def _dense_ctor(c, kernel_init):
    """This family's dense layers: biased (the GPT-2 shape), each site
    keeping its original `kernel_init`, routed through the shared quant
    dispatch (`precision.quant.make_dense`) so `c.quant == "int8"`
    swaps in `QuantDenseGeneral` (bias stays float) everywhere.
    `nn.DenseGeneral(features=int, axis=-1)` is exactly `nn.Dense`
    (same param leaves), so float checkpoints and training dynamics are
    unaffected by the shared ctor."""
    from hyperion_tpu.precision.quant import make_dense

    return make_dense(c, kernel_init=kernel_init, use_bias=True)


class MHA(nn.Module):
    cfg: TransformerLMConfig

    @nn.compact
    def __call__(self, x, padding_mask, deterministic: bool):
        c = self.cfg
        B, T, _ = x.shape
        dense = partial(
            _dense_ctor(c, nn.initializers.xavier_uniform()),
            features=(c.n_heads, c.head_dim),
        )
        # stretches of the call as `jax.named_scope`s, the names
        # models/llama.py uses: a scope names device time in a trace and
        # moves no parameter path (a sub-module would)
        with jax.named_scope("qkv_proj"):
            q = dense(name="q_proj")(x)
            k = dense(name="k_proj")(x)
            v = dense(name="v_proj")(x)
        with jax.named_scope("attention"):
            out = dot_product_attention(
                q, k, v, causal=c.causal, padding_mask=padding_mask,
                impl=c.attention_impl
            )
        with jax.named_scope("o_proj"):
            return _dense_ctor(c, nn.initializers.xavier_uniform())(
                features=c.d_model,
                axis=(-2, -1),
                name="o_proj",
            )(out)


class FusedLayerNorm(nn.Module):
    """nn.LayerNorm-compatible module (same `scale`/`bias` params, so
    checkpoints swap freely between impls) backed by the Pallas
    `fused_layernorm` kernel — the norm half of the `jit+pallas` tier."""

    dtype: jnp.dtype
    eps: float = 1e-6  # nn.LayerNorm default, for param/output parity

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        return fused_layernorm(x.astype(self.dtype), scale, bias, eps=self.eps)


def _norm(cfg, name: str):
    if cfg.norm_impl == "pallas":
        return FusedLayerNorm(dtype=cfg.compute_dtype, name=name)
    return nn.LayerNorm(dtype=cfg.compute_dtype, name=name)


class Block(nn.Module):
    cfg: TransformerLMConfig

    @nn.compact
    def __call__(self, x, padding_mask, deterministic: bool):
        c = self.cfg
        act = {"relu": nn.relu, "gelu": nn.gelu}[c.activation]
        h = _norm(c, "ln1")(x)
        h = MHA(c, name="attn")(h, padding_mask, deterministic)
        h = nn.Dropout(c.dropout, deterministic=deterministic)(h)
        x = x + h
        h = _norm(c, "ln2")(x)
        mlp_init = nn.initializers.lecun_normal()  # the nn.Dense default
        h = _dense_ctor(c, mlp_init)(features=c.ff_dim, name="fc1")(h)
        h = act(h)
        h = _dense_ctor(c, mlp_init)(features=c.d_model, name="fc2")(h)
        h = nn.Dropout(c.dropout, deterministic=deterministic)(h)
        return x + h


def remat_block_cls(cfg: TransformerLMConfig, block_cls=None):
    """Block class (default `Block`) wrapped per cfg.remat_policy — the
    activation-checkpointing knob both LM variants must honour."""
    block_cls = block_cls or Block
    if cfg.remat_policy == "none":
        return block_cls
    from hyperion_tpu.precision.remat import REMAT_POLICIES

    return nn.remat(
        block_cls, static_argnums=(3,),
        policy=REMAT_POLICIES[cfg.remat_policy],
    )


def lm_backbone(c: TransformerLMConfig, input_ids, padding_mask,
                deterministic: bool, make_block):
    """Shared LM scaffold (embeddings → blocks → final norm → head),
    used by TransformerLM and MoELM so the two cannot drift. Must be
    called from inside an @nn.compact __call__; `make_block(i)` returns
    the (possibly remat-wrapped) block module for layer i, already
    named."""
    T = input_ids.shape[1]
    if T > c.max_len:
        raise ValueError(
            f"sequence length {T} exceeds max_len {c.max_len} — the "
            f"positional table has no rows past max_len"
        )
    with jax.named_scope("embed"):
        x = nn.Embed(
            c.vocab_size,
            c.d_model,
            dtype=c.compute_dtype,
            embedding_init=nn.initializers.normal(0.02),
            name="tok_emb",
        )(input_ids)
        pos = nn.Embed(
            c.max_len,
            c.d_model,
            dtype=c.compute_dtype,
            embedding_init=nn.initializers.normal(0.02),
            name="pos_emb",
        )(jnp.arange(T, dtype=jnp.int32))
        x = x + pos[None]
        x = nn.Dropout(c.dropout, deterministic=deterministic)(x)
    for i in range(c.n_layers):
        x = make_block(i)(x, padding_mask, deterministic)
    x = _norm(c, "ln_f")(x)
    with jax.named_scope("lm_head"):
        logits = _dense_ctor(c, nn.initializers.normal(0.02))(
            features=c.vocab_size,
            name="lm_head",
        )(x)
        return logits.astype(jnp.float32)


class TransformerLM(nn.Module):
    cfg: TransformerLMConfig

    @nn.compact
    def __call__(self, input_ids, padding_mask=None, deterministic: bool = True):
        """input_ids: int32 [B, T] → logits fp32 [B, T, vocab]."""
        c = self.cfg
        block = remat_block_cls(c)
        return lm_backbone(
            c, input_ids, padding_mask, deterministic,
            lambda i: block(c, name=f"block_{i}"),
        )

    def init_params(self, rng: jax.Array, batch: int = 2):
        ids = jnp.zeros((batch, self.cfg.max_len), jnp.int32)
        return self.init(rng, ids)["params"]
