"""Device-free TPU (Mosaic) LOWERING guards for the Pallas kernels.

`jax.export` with `platforms=["tpu"]` runs Mosaic's block-shape check
(`_check_block_mappings`: "the last two dimensions of your block shape
are divisible by 8 and 128 ...") on the host with no TPU attached, so
these tests fail the moment a kernel's BlockSpec goes Mosaic-illegal.

That is all they prove. Lowering is not the chip's compiler: fused CE
passed this guard and was refused by the real compile (a layout
disagreement over its 1-D operands), which only
`tests/test_tpu_compile.py` — a whole compile against a described v5e —
could see. These stay as the cheaper, narrower check (no TPU library is
loaded, so they may run in any worker).

Each test monkeypatches the kernel module's `_interpret` gate to False:
without that, a CPU test session would export the interpreter path and
prove nothing.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import pytest
from jax import export

S = jax.ShapeDtypeStruct


def _force_mosaic(monkeypatch, *modules: str):
    for name in modules:
        monkeypatch.setattr(
            sys.modules[f"hyperion_tpu.ops.pallas.{name}"],
            "_interpret", lambda: False,
        )


def _export_tpu(fn, *avals):
    export.export(jax.jit(fn), platforms=["tpu"])(*avals)


class TestFlashAttentionLowering:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_fwd_bwd_lowers(self, monkeypatch, causal, masked):
        import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401

        _force_mosaic(monkeypatch, "flash_attention")
        from hyperion_tpu.ops.pallas.flash_attention import flash_attention

        B, T, H, D = 2, 128, 4, 64  # head_dim 64: the gpt2-family shape
        mask = jnp.ones((B, T), jnp.int32) if masked else None

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, padding_mask=mask)
            return (out.astype(jnp.float32) ** 2).sum()

        fn = lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        a = S((B, T, H, D), jnp.bfloat16)
        _export_tpu(fn, a, a, a)

    def test_long_seq_d128_lowers(self, monkeypatch):
        import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401

        _force_mosaic(monkeypatch, "flash_attention")
        from hyperion_tpu.ops.pallas.flash_attention import flash_attention

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum()

        fn = lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        a = S((1, 4096, 8, 128), jnp.bfloat16)  # attention_bench shape
        _export_tpu(fn, a, a, a)


class TestFusedNormLowering:
    def test_layernorm_residual_lowers(self, monkeypatch):
        import hyperion_tpu.ops.pallas.fused_norm  # noqa: F401

        _force_mosaic(monkeypatch, "fused_norm")
        from hyperion_tpu.ops.pallas.fused_norm import fused_layernorm

        def loss(x, r, w, b):
            return (fused_layernorm(x, w, b, residual=r) ** 2).sum()

        fn = lambda x, r, w, b: jax.grad(loss, argnums=(0, 1, 2, 3))(x, r, w, b)
        x = S((32, 128, 768), jnp.float32)
        v = S((768,), jnp.float32)
        _export_tpu(fn, x, x, v, v)

    def test_rmsnorm_lowers(self, monkeypatch):
        import hyperion_tpu.ops.pallas.fused_norm  # noqa: F401

        _force_mosaic(monkeypatch, "fused_norm")
        from hyperion_tpu.ops.pallas.fused_norm import fused_rmsnorm

        def loss(x, w):
            return (fused_rmsnorm(x, w) ** 2).sum()

        fn = lambda x, w: jax.grad(loss, argnums=(0, 1))(x, w)
        _export_tpu(fn, S((32, 128, 768), jnp.float32), S((768,), jnp.float32))


class TestFusedCELowering:
    def test_fwd_bwd_lowers_gpt2_vocab(self, monkeypatch):
        import hyperion_tpu.ops.pallas.fused_ce  # noqa: F401

        _force_mosaic(monkeypatch, "fused_ce")
        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        def loss(logits, targets):
            return fused_softmax_xent(logits, targets).mean()

        fn = lambda lg, tg: jax.grad(loss)(lg, tg)
        _export_tpu(fn, S((4064, 50257), jnp.float32), S((4064,), jnp.int32))


@pytest.mark.slow
class TestFullModelLowering:
    """The compile_bench jit_pallas tier, proven lowerable end-to-end."""

    def test_gpt2_lm_pallas_train_grad(self, monkeypatch):
        import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401
        import hyperion_tpu.ops.pallas.fused_norm  # noqa: F401

        _force_mosaic(monkeypatch, "flash_attention", "fused_norm")
        import optax

        from hyperion_tpu.models.transformer_lm import (
            TransformerLM, gpt2_lm_config,
        )

        model = TransformerLM(gpt2_lm_config(
            dropout=0.0, dtype="bfloat16",
            attention_impl="pallas", norm_impl="pallas",
        ))
        params = jax.eval_shape(
            lambda: model.init_params(jax.random.key(0), batch=2)
        )

        def loss(p, x):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), x[:, 1:]).mean()

        _export_tpu(
            lambda p, x: jax.grad(loss)(p, x),
            params, S((8, 128), jnp.int32),
        )

    def test_llama_pallas_train_grad(self, monkeypatch):
        import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401
        import hyperion_tpu.ops.pallas.fused_norm  # noqa: F401

        _force_mosaic(monkeypatch, "flash_attention", "fused_norm")
        import optax

        from hyperion_tpu.models.llama import Llama, LlamaConfig

        cfg = LlamaConfig(
            vocab_size=1000, d_model=256, n_heads=4, n_kv_heads=4,
            n_layers=2, ff_dim=512, max_len=128, dtype="bfloat16",
            attention_impl="pallas", norm_impl="pallas", remat=False,
        )
        lm = Llama(cfg)
        params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0)))

        def loss(p, x):
            logits = lm.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), x[:, 1:]).mean()

        _export_tpu(
            lambda p, x: jax.grad(loss)(p, x),
            params, S((8, 128), jnp.int32),
        )
