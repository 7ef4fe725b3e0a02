"""Pallas kernel correctness vs the XLA reference formulation.

Runs in interpret mode on the CPU backend (the kernels detect non-TPU
backends themselves), so the same tests validate the real kernels on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.ops.attention import (
    dot_product_attention,
    select_attention_impl,
)
from hyperion_tpu.ops.pallas.flash_attention import (
    default_blocks,
    flash_attention,
)
from hyperion_tpu.ops.pallas.fused_norm import fused_layernorm, fused_rmsnorm


def qkv(shape=(2, 64, 4, 16), seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(k, shape, dtype) for k in ks]


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla(self, causal):
        q, k, v = qkv()
        ref = dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_padding_mask(self):
        q, k, v = qkv()
        mask = np.ones((2, 64), np.int8)
        mask[:, 48:] = 0
        ref = dot_product_attention(q, k, v, causal=True,
                                    padding_mask=jnp.asarray(mask))
        out = flash_attention(q, k, v, causal=True,
                              padding_mask=jnp.asarray(mask),
                              block_q=32, block_kv=32)
        # only compare non-pad query rows (pad rows are don't-care)
        np.testing.assert_allclose(np.asarray(out)[:, :48],
                                   np.asarray(ref)[:, :48],
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_xla(self):
        q, k, v = qkv(shape=(1, 32, 2, 8))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=16, block_kv=16) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_grad_with_mask_does_not_crash(self):
        q, k, v = qkv(shape=(1, 32, 2, 8))
        mask = jnp.asarray(np.ones((1, 32), np.int8))

        def loss(q):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           padding_mask=mask,
                                           block_q=16, block_kv=16))

        g = jax.grad(loss)(q)
        assert bool(jnp.isfinite(g).all())

    def test_gradients_match_xla_with_padding(self):
        """Padded positions excluded from the loss (as any masked LM
        loss does) — gradients must match the XLA reference."""
        q, k, v = qkv(shape=(2, 32, 2, 8))
        mask_np = np.ones((2, 32), np.int8)
        mask_np[0, 24:] = 0
        mask_np[1, 16:] = 0
        mask = jnp.asarray(mask_np)
        w = jnp.asarray(mask_np, jnp.float32)[:, :, None, None]

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, padding_mask=mask,
                                  block_q=16, block_kv=16)
            return jnp.sum((out * w) ** 2)

        def loss_ref(q, k, v):
            out = dot_product_attention(q, k, v, causal=True,
                                        padding_mask=mask)
            return jnp.sum((out * w) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_gradients_multiblock_long_seq(self):
        """Causality and accumulation across many kv/q tiles (8x8 grid
        of blocks) — the streaming path the VMEM design exists for."""
        q, k, v = qkv(shape=(1, 256, 2, 16), seed=3)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True,
                                  block_q=32, block_kv=32)
            return jnp.sum(out ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        np.testing.assert_allclose(
            float(loss_flash(q, k, v)), float(loss_ref(q, k, v)), rtol=1e-5
        )
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_bf16_grads_finite(self):
        q, k, v = qkv(shape=(1, 64, 2, 16), dtype=jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True,
                                block_q=32, block_kv=32).astype(jnp.float32)
            )

        gs = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in gs:
            assert g.dtype == jnp.bfloat16
            assert bool(jnp.isfinite(g.astype(jnp.float32)).all())

    def test_model_integration(self):
        """attention_impl='pallas' must be numerically equivalent."""
        from hyperion_tpu.models.transformer_lm import TransformerLM, simple_lm_config

        kw = dict(vocab_size=128, d_model=32, n_heads=2, n_layers=1,
                  ff_dim=64, max_len=32, dropout=0.0)
        xla = TransformerLM(simple_lm_config(attention_impl="xla", **kw))
        pls = TransformerLM(simple_lm_config(attention_impl="pallas", **kw))
        params = xla.init_params(jax.random.key(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)),
                          jnp.int32)
        a = xla.apply({"params": params}, ids)
        b = pls.apply({"params": params}, ids)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)

    def test_indivisible_block_falls_back(self):
        # seq 48 doesn't divide the requested 32: _pick_block falls back
        # to a legal tiling (here one 48-wide tile) instead of raising
        q, k, v = qkv(shape=(1, 48, 2, 8))
        out = flash_attention(q, k, v, block_q=32, block_kv=32)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_wide_single_tile_fallback_warns(self):
        # an indivisible mid-length sequence still runs, but no longer
        # silently: one 1100-wide fp32 logits tile is near the 2048^2
        # VMEM edge the module documents (ADVICE r4)
        from hyperion_tpu.ops.pallas.flash_attention import _pick_block

        with pytest.warns(UserWarning, match="1100-wide tile"):
            assert _pick_block(1100, 1024) == 1100
        # short fallbacks stay silent
        assert _pick_block(48, 32) == 48

    def test_mixed_dtypes_reconciled_to_q(self):
        # bf16 q with fp32 k/v (e.g. a half-converted cache) computes in
        # q's dtype instead of raising — parity with the XLA impl's
        # q-dtype compute (ADVICE r4)
        q, k, v = qkv(shape=(1, 32, 2, 8))
        out = flash_attention(q.astype(jnp.bfloat16), k, v,
                              block_q=16, block_kv=16)
        assert out.dtype == jnp.bfloat16
        ref = flash_attention(q.astype(jnp.bfloat16),
                              k.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16),
                              block_q=16, block_kv=16)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=1e-5, rtol=1e-5)

    def test_default_blocks_head_dim_aware(self):
        # D=64 keeps the swept 1024x1024; D=128 (Llama) halves block_kv
        # until an on-chip D=128 sweep validates wider (ADVICE r4)
        assert default_blocks(64) == (1024, 1024)
        assert default_blocks(128) == (1024, 512)


class TestAttentionImplAutoSelect:
    """Geometry-aware impl="auto" resolution (VERDICT r4 item 6)."""

    def test_short_seq_keeps_xla(self):
        assert select_attention_impl(128, 64) == "xla"
        assert select_attention_impl(2048, 64) == "xla"

    def test_long_train_gets_pallas(self):
        assert select_attention_impl(4096, 64) == "pallas"
        assert select_attention_impl(16384, 128) == "pallas"

    def test_fwd_mode_crossover_is_higher(self):
        assert select_attention_impl(4096, 64, mode="fwd") == "xla"
        assert select_attention_impl(8192, 64, mode="fwd") == "pallas"

    def test_unprobed_geometry_stays_xla(self):
        assert select_attention_impl(4096, 256) == "xla"       # big head
        assert select_attention_impl(4100, 64) == "xla"        # not 128-mult

    def test_auto_dispatches_through_attention(self):
        # short seq through impl="auto" matches the xla path exactly
        q, k, v = qkv(shape=(1, 32, 2, 8))
        auto = dot_product_attention(q, k, v, causal=True, impl="auto")
        ref = dot_product_attention(q, k, v, causal=True, impl="xla")
        np.testing.assert_allclose(np.asarray(auto), np.asarray(ref))

    def test_tier_default_is_auto(self):
        from hyperion_tpu.config import Config
        from hyperion_tpu.train.trainer import _tier_impls

        cfg = Config()
        cfg.optimization.compile_tier = "jit+pallas"
        assert _tier_impls(cfg)["attention_impl"] == "auto"
        cfg.optimization.attention_impl = "pallas"  # explicit wins
        assert _tier_impls(cfg)["attention_impl"] == "pallas"


class TestFusedLayerNorm:
    def test_matches_lax_layernorm(self):
        x = jax.random.normal(jax.random.key(0), (4, 16, 32))
        w = jax.random.normal(jax.random.key(1), (32,)) + 1.0
        b = jax.random.normal(jax.random.key(2), (32,))
        out = fused_layernorm(x, w, b)
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        ref = (x - mean) / jnp.sqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_residual_fusion(self):
        x = jax.random.normal(jax.random.key(0), (8, 32))
        r = jax.random.normal(jax.random.key(1), (8, 32))
        w = jnp.ones(32)
        b = jnp.zeros(32)
        out = fused_layernorm(x, w, b, residual=r)
        ref = fused_layernorm(x + r, w, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_gradients(self):
        x = jax.random.normal(jax.random.key(0), (8, 16))
        w = jnp.ones(16)
        b = jnp.zeros(16)

        def loss(x, w, b):
            return jnp.sum(fused_layernorm(x, w, b) ** 2)

        def ref_loss(x, w, b):
            mean = x.mean(-1, keepdims=True)
            var = x.var(-1, keepdims=True)
            return jnp.sum(((x - mean) / jnp.sqrt(var + 1e-5) * w + b) ** 2)

        ga = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        gb = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)

    def test_rmsnorm_matches_reference(self):
        x = jax.random.normal(jax.random.key(0), (4, 16, 32))
        w = jax.random.normal(jax.random.key(1), (32,)) + 1.0
        out = fused_rmsnorm(x, w, eps=1e-5)
        ref = x * jax.lax.rsqrt(jnp.mean(x**2, -1, keepdims=True) + 1e-5) * w
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_rmsnorm_gradients(self):
        x = jax.random.normal(jax.random.key(0), (8, 16))
        w = jnp.ones(16) * 1.5

        def loss(x, w):
            return jnp.sum(fused_rmsnorm(x, w) ** 2)

        def ref_loss(x, w):
            y = x * jax.lax.rsqrt(jnp.mean(x**2, -1, keepdims=True) + 1e-5) * w
            return jnp.sum(y ** 2)

        ga = jax.grad(loss, argnums=(0, 1))(x, w)
        gb = jax.grad(ref_loss, argnums=(0, 1))(x, w)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_llama_norm_impl_equivalence(self):
        """norm_impl='pallas' must match the XLA RMSNorm in-model."""
        from hyperion_tpu.models.llama import Llama, llama_tiny_config

        xla = Llama(llama_tiny_config(norm_impl="xla"))
        pls = Llama(llama_tiny_config(norm_impl="pallas"))
        params = xla.init_params(jax.random.key(0), seq=32)
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)),
                          jnp.int32)
        a = xla.apply({"params": params}, ids)
        b = pls.apply({"params": params}, ids)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)

    def test_lm_full_pallas_tier_equivalence(self):
        """attention_impl + norm_impl both pallas ≡ both xla."""
        from hyperion_tpu.models.transformer_lm import TransformerLM, simple_lm_config

        kw = dict(vocab_size=128, d_model=32, n_heads=2, n_layers=2,
                  ff_dim=64, max_len=32, dropout=0.0)
        xla = TransformerLM(simple_lm_config(**kw))
        pls = TransformerLM(simple_lm_config(
            attention_impl="pallas", norm_impl="pallas", **kw))
        params = xla.init_params(jax.random.key(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)),
                          jnp.int32)
        a = xla.apply({"params": params}, ids)
        b = pls.apply({"params": params}, ids)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)

    def test_bf16_stats_in_fp32(self):
        x = (jax.random.normal(jax.random.key(0), (4, 64)) * 100).astype(jnp.bfloat16)
        out = fused_layernorm(x, jnp.ones(64), jnp.zeros(64))
        assert out.dtype == jnp.bfloat16
        # normalized rows: mean ~0, std ~1 even for large-magnitude input
        f = np.asarray(out, np.float32)
        assert abs(f.mean()) < 0.1
        assert abs(f.std() - 1.0) < 0.1


class TestFusedCrossEntropy:
    """fused_softmax_xent vs optax: values, grads, padding, dtypes."""

    def _data(self, n=12, v=300, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        logits = jnp.asarray(rng.normal(size=(n, v)) * 3, dtype)
        targets = jnp.asarray(rng.integers(0, v, n), jnp.int32)
        return logits, targets

    def test_matches_optax(self):
        import optax

        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        logits, targets = self._data()
        ref = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        )
        out = fused_softmax_xent(logits, targets)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_odd_shapes_pad_correctly(self):
        """N and V far from tile multiples: padding columns (NEG_INF)
        and rows must not change values."""
        import optax

        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        logits, targets = self._data(n=7, v=131)
        ref = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        out = fused_softmax_xent(logits, targets, 4, 64)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_grads_match_optax(self):
        import optax

        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        logits, targets = self._data(n=9, v=200)
        w = jnp.asarray(np.random.default_rng(1).random(9), jnp.float32)

        def loss_f(fn):
            return lambda lg: jnp.sum(fn(lg, targets) * w)

        g_ref = jax.grad(loss_f(
            lambda lg, t: optax.softmax_cross_entropy_with_integer_labels(lg, t)
        ))(logits)
        g = jax.grad(loss_f(
            lambda lg, t: fused_softmax_xent(lg, t, 4, 64)
        ))(logits)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), atol=1e-5, rtol=1e-4
        )

    def test_bf16_logits_finite(self):
        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        logits, targets = self._data(dtype=jnp.bfloat16)
        out = fused_softmax_xent(logits, targets)
        assert out.dtype == jnp.float32
        assert np.isfinite(np.asarray(out)).all()
        g = jax.grad(lambda lg: fused_softmax_xent(lg, targets).sum())(logits)
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, np.float32)).all()

    def test_next_token_loss_impl_parity(self):
        from hyperion_tpu.train.losses import next_token_loss

        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(2, 10, 257)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 257, (2, 10)), jnp.int32)
        mask = jnp.asarray(rng.random((2, 10)) > 0.2, jnp.int8)
        ref = next_token_loss(logits, ids, mask)
        out = next_token_loss(logits, ids, mask, impl="pallas")
        np.testing.assert_allclose(float(out), float(ref), atol=1e-5, rtol=1e-5)


class TestPagedAttention:
    """Paged decode kernel (ops/pallas/paged_attention) vs the gather
    path it replaces: the kernel walks the [S, MB] block table in-kernel
    via scalar prefetch; the oracle gathers pool[bt] into the contiguous
    view and runs the same masked grouped attention the model uses. The
    online softmax reorders the fp reduction, so parity is
    pinned-tolerance (fp32: 2e-5; observed ~2e-7 at op level), not
    bit-exact — the bound the kernel docstring documents."""

    def _ref(self, q, kp, vp, bt, base):
        # the llama.py gather read, shape-for-shape
        from hyperion_tpu.models.llama import (
            _chain_view,
            _grouped_cache_attention,
        )

        B, T, H, D = q.shape
        Hkv, bs, MB = kp.shape[1], kp.shape[2], bt.shape[1]
        L = MB * bs
        vk, vv = _chain_view(kp, bt), _chain_view(vp, bt)
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1)
        q_pos = base[:, None, None] + \
            jax.lax.broadcasted_iota(jnp.int32, (T, L), 0)[None]
        return _grouped_cache_attention(q, vk, vv, kv_pos[None] <= q_pos,
                                        H // Hkv)

    def _geometry(self, B, T, H, Hkv, D=16, bs=4, MB=8, seed=0,
                  share_prefix=False):
        """Pools + per-row block chains at random depths; unmapped tail
        entries stay 0 (the null block), exactly as serve/blocks.py
        hands them to the model."""
        NB = B * MB + 1
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
        kp = jax.random.normal(ks[1], (NB, Hkv, bs, D), jnp.float32)
        vp = jax.random.normal(ks[2], (NB, Hkv, bs, D), jnp.float32)
        rng = np.random.default_rng(seed)
        bt = np.zeros((B, MB), np.int32)
        base = rng.integers(0, MB * bs - T + 1, B).astype(np.int32)
        for b in range(B):
            n = (int(base[b]) + T + bs - 1) // bs
            bt[b, :n] = rng.permutation(np.arange(1, NB))[:n]
        if share_prefix:
            # COW-shared prefix: every row's first block is the SAME
            # physical block (a radix-cache hit before any divergence)
            bt[:, 0] = bt[0, 0]
        return q, kp, vp, jnp.asarray(bt), jnp.asarray(base)

    def _check(self, *geo, **kw):
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        q, kp, vp, bt, base = self._geometry(*geo, **kw)
        out = paged_attention(q, kp, vp, bt, base)
        ref = self._ref(q, kp, vp, bt, base)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_sequential_decode(self):        # [S, 1]
        self._check(3, 1, 4, 4)

    def test_speculative_verify(self):       # [S, k+1]
        self._check(3, 5, 4, 4, seed=1)

    def test_chunked_prefill(self):          # [1, C] at a mid-chain base
        self._check(1, 16, 4, 4, seed=2)

    def test_gqa_groups(self):               # rep = 4: 8 q heads, 2 kv
        self._check(2, 3, 8, 2, seed=3)

    def test_prefix_shared_chain(self):
        self._check(3, 2, 4, 4, seed=4, share_prefix=True)

    @pytest.mark.parametrize("segment", [0, 1, 2])
    def test_one_query_row_a_head_through_a_shifted_table(self, segment):
        """A looped model's tick (models/ouro.py): plain multi-head, 16
        query heads on 16 KV heads (ONE query row a KV head, padded to
        a sublane tile), and pools that hold three segments behind one
        table: `paged_read` hands the kernel the table shifted by
        `segment * NB`, null entries too, and the kernel stays as it
        is. Against the gather through the same shifted table, and
        against the segment cut out of the pools and read through the
        table as it was."""
        from hyperion_tpu.models.llama import paged_read, segment_shift

        B, H, MB, segs = 3, 16, 8, 3
        q, kp, vp, bt, base = self._geometry(B, 1, H, H, MB=MB, seed=7)
        NB = kp.shape[0]
        ks = jax.random.split(jax.random.key(8), 2)
        kp = jax.random.normal(ks[0], (segs * NB, *kp.shape[1:]))
        vp = jax.random.normal(ks[1], (segs * NB, *vp.shape[1:]))
        shift = segment_shift(kp, jnp.int32(segment), segs)
        assert int(shift) == segment * NB
        out = paged_read("pallas", q, kp, vp, bt, base, shift=shift)
        ref = paged_read("gather", q, kp, vp, bt, base, shift=shift)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        cut = slice(segment * NB, (segment + 1) * NB)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(self._ref(q, kp[cut], vp[cut], bt, base)),
            atol=2e-5, rtol=2e-5)

    def test_null_block_garbage_never_leaks(self):
        """Poison the null block with huge garbage: outputs must be
        BIT-identical to a zeroed null block — masked positions
        underflow to exactly 0 weight (finite NEG_INF), and blocks past
        the frontier are skipped outright."""
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        q, kp, vp, bt, base = self._geometry(3, 2, 4, 4, seed=5)
        assert int(np.asarray(bt == 0).sum()) > 0  # unmapped tails exist
        clean = paged_attention(q, kp, vp, bt, base)
        poisoned = paged_attention(
            q, kp.at[0].set(1e4), vp.at[0].set(-1e4), bt, base)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))

    # The walk's edges at GQA rep 4, groups of G = 4 blocks over a table
    # of 12 (block size 4): `base` of each of three rows, T = 1. A row's
    # live blocks are ceil((base + 1) / 4); None is an inactive lane (an
    # all-null table, whatever length its last occupant left behind).
    WALK_EDGES = {
        "ends_inside_a_group": (21, 5, 30),        # 6, 2, 8 blocks
        "ends_on_a_group_edge": (15, 31, 7),       # 4, 8, 2: whole groups
        "one_position": (0, 0, 0),                 # 1 block, 1 key
        "fills_the_table": (47, 47, 47),           # 12 blocks, 3 groups
        "inactive_lane_base_0": (None, 17, None),
        "inactive_lane_stale_base": (None, 9, None),
        "fewer_blocks_than_a_group": (8, 3, 11),   # 3, 1, 3 blocks
        "group_edge_plus_one_block": (16, 32, 33),  # 5, 9, 9 blocks
    }

    def _edge_geometry(self, bases, stale=0, T=1, H=8, Hkv=2, D=16,
                       bs=4, MB=12, seed=7):
        B = len(bases)
        NB = B * MB + 1
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
        kp = jax.random.normal(ks[1], (NB, Hkv, bs, D), jnp.float32)
        vp = jax.random.normal(ks[2], (NB, Hkv, bs, D), jnp.float32)
        bt = np.zeros((B, MB), np.int32)
        base = np.zeros((B,), np.int32)
        for b, n in enumerate(bases):
            if n is None:
                base[b] = stale
                continue
            base[b] = n
            live = (n + T + bs - 1) // bs
            bt[b, :live] = 1 + b * MB + np.arange(live)
        return q, kp, vp, jnp.asarray(bt), jnp.asarray(base)

    @pytest.mark.parametrize("edge", sorted(WALK_EDGES))
    def test_walk_edges(self, edge):
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        bases = self.WALK_EDGES[edge]
        q, kp, vp, bt, base = self._edge_geometry(
            bases, stale=29 if "stale" in edge else 0)
        out = np.asarray(paged_attention(q, kp, vp, bt, base,
                                         blocks_per_group=4))
        ref = np.asarray(self._ref(q, kp, vp, bt, base))
        live = [b for b, n in enumerate(bases) if n is not None]
        np.testing.assert_allclose(out[live], ref[live],
                                   atol=2e-5, rtol=2e-5)
        # an inactive lane's row is the caller's to ignore, but it is
        # a number: nothing uninitialised was multiplied
        assert np.isfinite(out).all()

    def test_garbage_past_the_frontier_never_leaks(self):
        """Garbage in the null block AND in the mapped positions past
        each row's frontier inside its last live block (a previous
        occupant's keys): outputs BIT-identical to clean pools, over
        several groups and with a short last group."""
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        bases, bs = (21, 5, 30), 4
        q, kp, vp, bt, base = self._edge_geometry(bases)
        clean = paged_attention(q, kp, vp, bt, base, blocks_per_group=4)
        kd, vd = np.array(kp), np.array(vp)
        kd[0], vd[0] = 1e4, -1e4
        poisoned = 0
        for b, n in enumerate(bases):
            last = int(bt[b, n // bs])
            kd[last, :, n % bs + 1:] = 1e4
            vd[last, :, n % bs + 1:] = -1e4
            poisoned += bs - 1 - n % bs
        assert poisoned > 0
        dirty = paged_attention(q, jnp.asarray(kd), jnp.asarray(vd), bt,
                                base, blocks_per_group=4)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))

    # The windowed walk (a sliding-attention layer's pool), window 20
    # over blocks of 4 in a table of 16: `base` of each of three rows.
    # A row's table is what the engine leaves of it: entries before the
    # block of `base - window + 1` are null (let go), entries up to the
    # block of `base + T - 1` mapped. None is a lane the tick masks out.
    WINDOW = 20
    WINDOW_WALKS = {
        "shorter_than_the_window": (3, 0, 12),
        "ends_on_a_block_edge": (19, 39, 59),      # base + T: whole blocks
        "several_windows_long": (55, 41, 30),      # leading entries null
        "masked_out_lane": (None, 44, None),       # stale base 57
    }

    def _window_geometry(self, bases, T, stale=57, bs=4, MB=16):
        q, kp, vp, bt, base = self._edge_geometry(
            [None if n is None else n + T - 1 for n in bases],
            stale=stale, T=1, bs=bs, MB=MB)
        q = jnp.tile(q, (1, T, 1, 1)) * (
            1 + jnp.arange(T, dtype=jnp.float32))[None, :, None, None]
        bt, base = np.array(bt), np.array(base)
        for b, n in enumerate(bases):
            if n is not None:
                base[b] = n
                bt[b, :max(n - self.WINDOW + 1, 0) // bs] = 0
        # whatever the null block holds is never seen
        return (q, kp.at[0].set(1e4), vp.at[0].set(-1e4),
                jnp.asarray(bt), jnp.asarray(base))

    # blocks a DMA group: 1 (a later token's window can start beyond
    # the first group), 2, and the plan's (the whole walk)
    @pytest.mark.parametrize("G", [1, 2, None])
    @pytest.mark.parametrize("T", [1, 4])
    @pytest.mark.parametrize("walk", sorted(WINDOW_WALKS))
    def test_windowed_walk(self, walk, T, G):
        """`window > 0` against the gather read of a windowed layer
        (`paged_gather_read(first=, window=)`): the walk starts at the
        block of the first position the first query sees, entries
        before it may be null, and a query at p sees p - window < j <=
        p. With groups of 1 or 2 blocks a slot's walk (up to 7) is
        several groups, the last one short."""
        from hyperion_tpu.models.llama import paged_gather_read
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        bases, W = self.WINDOW_WALKS[walk], self.WINDOW
        if walk == "ends_on_a_block_edge":
            bases = tuple(n + 1 - T for n in bases)
        q, kp, vp, bt, base = self._window_geometry(bases, T)
        live = [b for b, n in enumerate(bases) if n is not None]
        if walk == "several_windows_long":
            assert all(int(bt[b, 0]) == 0 for b in live)
        out = np.asarray(paged_attention(
            q, kp, vp, bt, base, window=W, blocks_per_group=G))
        ref = np.asarray(paged_gather_read(
            q, kp, vp, bt, base, jnp.maximum(base - W + 1, 0), W))
        np.testing.assert_allclose(out[live], ref[live],
                                   atol=2e-5, rtol=2e-5)
        assert np.isfinite(out).all()
        # and the window term is there: the full walk of the same
        # chains answers otherwise once a context passes the window
        if walk == "several_windows_long":
            full = np.asarray(self._ref(q, kp, vp, bt, base))
            assert np.abs(out - full).max() > 1e-3

    @pytest.mark.parametrize("edge", sorted(WALK_EDGES))
    def test_window_0_is_the_full_walk(self, edge):
        """`window=0` is the call without the keyword, and a window no
        context outgrows changes nothing either: the same walk from
        block 0, bit for bit."""
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        q, kp, vp, bt, base = self._edge_geometry(
            self.WALK_EDGES[edge], stale=29 if "stale" in edge else 0)
        today = np.asarray(paged_attention(q, kp, vp, bt, base,
                                           blocks_per_group=4))
        for window in (0, bt.shape[1] * kp.shape[2]):
            np.testing.assert_array_equal(today, np.asarray(paged_attention(
                q, kp, vp, bt, base, window=window, blocks_per_group=4)))

    def test_model_level_matches_gather(self):
        """Full Llama tiny (GQA rep 2) through all three engine window
        shapes, caches threaded forward per impl: chunked prefill
        [1, C], speculative verify [S, k+1] at per-row depths, then
        sequential decode [S, 1]. Logits agree to the pinned fp32
        bound at every step; caches agree to the same bound (layer 0's
        scatter is shared code bit-for-bit, but deeper layers' K/V
        projections consume the previous layer's attention output,
        which carries the online-softmax reordering delta)."""
        import dataclasses

        from hyperion_tpu.models.llama import (
            Llama, init_paged_cache, llama_tiny_config)

        cfg = llama_tiny_config(n_kv_heads=2, max_len=16)
        bs, B = 4, 2
        MB = cfg.max_len // bs
        m_g = Llama(cfg)
        m_p = Llama(dataclasses.replace(cfg, paged_attn_impl="pallas"))
        params = m_g.init(jax.random.key(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
        caches = {"gather": init_paged_cache(cfg, B * MB + 1, bs),
                  "pallas": init_paged_cache(cfg, B * MB + 1, bs)}
        rng = np.random.default_rng(0)
        bt = np.zeros((B, MB), np.int32)
        bt[:] = rng.permutation(np.arange(1, B * MB + 1)).reshape(B, MB)
        bt = jnp.asarray(bt)

        def step(ids, index, tables):
            outs = {}
            for name, model in (("gather", m_g), ("pallas", m_p)):
                logits, caches[name] = model.apply(
                    {"params": params}, ids, cache=caches[name],
                    cache_index=index, block_tables=tables)
                outs[name] = logits
            np.testing.assert_allclose(
                np.asarray(outs["pallas"]), np.asarray(outs["gather"]),
                atol=2e-5, rtol=2e-5)
            for lg, lp in zip(caches["gather"], caches["pallas"]):
                np.testing.assert_allclose(np.asarray(lg["k"]),
                                           np.asarray(lp["k"]),
                                           atol=2e-5, rtol=2e-5)
                np.testing.assert_allclose(np.asarray(lg["v"]),
                                           np.asarray(lp["v"]),
                                           atol=2e-5, rtol=2e-5)

        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)),
                          jnp.int32)
        step(ids, 0, bt[:1])                              # [1, C] chunk
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 3)),
                          jnp.int32)
        step(ids, jnp.asarray([6, 0], jnp.int32), bt)     # [S, k+1]
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)),
                          jnp.int32)
        step(ids, jnp.asarray([9, 3], jnp.int32), bt)     # [S, 1]


WINDOW_HEADS = {"8x4": (8, 4), "8x6": (8, 6), "4x7": (4, 7), "16x1": (16, 1)}
# the first position of a 40-position window: a prompt's start, a
# chunk's multiple of the block, a prefix hit that ends inside a block
WINDOW_BASES = {"start": 0, "on_a_block": 96, "mid_block": 150}


class TestWindowAttention:
    """The tiled prompt-window kernel (ops/pallas/window_attention)
    through `paged_read("tiled")` against `paged_read("gather")`, the
    oracle: the same blocks of the same table, the same mask, an online
    softmax over key tiles in place of one softmax over the view.
    Float32 within the tick kernel's 2e-5; tiles of 16 positions and 128
    keys, so every case crosses query tiles, key tiles, the diagonal and
    (windowed) the window's far edge, and skips tiles at both ends."""

    T, bs, D, MB, WINDOW = 40, 4, 16, 112, 160   # a view of 448 keys

    @pytest.fixture(autouse=True)
    def small_tiles(self, monkeypatch):
        import hyperion_tpu.ops.pallas.window_attention as wa

        monkeypatch.setattr(wa, "_plan", lambda *a: (16, 128))

    def _geometry(self, Hkv, rep, base, window, *, B=1, T=None, seed=0,
                  dtype=jnp.float32, segments=1):
        """A live slot as the engine hands it over: its chain mapped
        from the block of the first position the window's first query
        sees (0 in a full layer) to the frontier `base + T`, null
        entries before (blocks let go) and beyond; a garbage null block
        of each segment."""
        T = T or self.T
        bs, D, MB = self.bs, self.D, self.MB
        NB = B * MB + 1
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (B, T, Hkv * rep, D), dtype)
        kp = jax.random.normal(ks[1], (segments * NB, Hkv, bs, D), dtype)
        vp = jax.random.normal(ks[2], (segments * NB, Hkv, bs, D), dtype)
        rng = np.random.default_rng(seed)
        bt = np.zeros((B, MB), np.int32)
        order = rng.permutation(np.arange(1, NB))
        for b in range(B):
            lo = max(base - window + 1, 0) // bs if window else 0
            hi = min(MB, -(-(base + T) // bs))
            bt[b, lo:hi] = order[b * MB + lo:b * MB + hi]
        return (q, kp, vp, jnp.asarray(bt),
                jnp.full((B,), base, jnp.int32))

    def _both(self, q, kp, vp, bt, base, window, shift=None):
        from hyperion_tpu.models.llama import paged_read

        return (paged_read("tiled", q, kp, vp, bt, base, window, shift),
                paged_read("gather", q, kp, vp, bt, base, window, shift))

    @pytest.mark.parametrize("base", sorted(WINDOW_BASES))
    @pytest.mark.parametrize("kind", ["full", "windowed"])
    @pytest.mark.parametrize("heads", sorted(WINDOW_HEADS))
    def test_matches_the_gather(self, heads, kind, base):
        Hkv, rep = WINDOW_HEADS[heads]
        window = self.WINDOW if kind == "windowed" else 0
        geo = self._geometry(Hkv, rep, WINDOW_BASES[base], window,
                             seed=len(heads + kind + base))
        out, ref = self._both(*geo, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kind", ["full", "windowed"])
    @pytest.mark.parametrize("heads", ["16x1", "8x4"])
    @pytest.mark.parametrize("segment", [0, 2])
    def test_through_a_shifted_table(self, segment, heads, kind):
        """A looped model's prompt window (models/ouro.py): pools of
        three segments behind one table, read through the table shifted
        by `segment * NB`, null entries and the view's padding too;
        against the gather through the same shift and against the
        segment cut out of the pools."""
        from hyperion_tpu.models.llama import segment_shift

        Hkv, rep = WINDOW_HEADS[heads]
        window = self.WINDOW if kind == "windowed" else 0
        q, kp, vp, bt, base = self._geometry(
            Hkv, rep, 150, window, seed=11, segments=3)
        shift = segment_shift(kp, jnp.int32(segment), 3)
        out, ref = self._both(q, kp, vp, bt, base, window, shift)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        NB = kp.shape[0] // 3
        cut = slice(segment * NB, (segment + 1) * NB)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(self._both(q, kp[cut], vp[cut], bt, base,
                                  window)[1]),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kind", ["full", "windowed"])
    def test_several_rows_each_at_its_own_base(self, kind):
        from hyperion_tpu.models.llama import paged_read

        window = self.WINDOW if kind == "windowed" else 0
        q, kp, vp, bt, _ = self._geometry(2, 3, 0, 0, B=3, seed=5)
        # every chain mapped whole: each row reads from its own depth
        bt = jnp.asarray(np.random.default_rng(5).permutation(
            np.arange(1, 3 * self.MB + 1)).reshape(3, self.MB)
            .astype(np.int32))
        base = jnp.asarray([0, 203, 408], jnp.int32)
        out = paged_read("tiled", q, kp, vp, bt, base, window)
        ref = paged_read("gather", q, kp, vp, bt, base, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", [
        "padding_past_the_frontier", "blocks_let_go", "inactive_lane"])
    def test_garbage_gets_weight_exactly_zero(self, case):
        """What the table does not cover reads the null block, and what
        the null block holds is garbage by contract: a prompt of 21
        positions in its bucket of 40 (the bucket's tail is written to
        block 0 and lies past every real query), the blocks a windowed
        layer has let go before the window, a lane with an all-null
        table and its last occupant's length. Huge values there change
        no real row by a bit, and every row stays finite."""
        from hyperion_tpu.models.llama import paged_read

        window = self.WINDOW if case == "blocks_let_go" else 0
        base, real = 203, 21 if case == "padding_past_the_frontier" \
            else self.T
        q, kp, vp, bt, b = self._geometry(2, 4, base, window, T=real,
                                          seed=9)
        q = jnp.concatenate(
            [q, jnp.ones((1, self.T - real, *q.shape[2:]))], axis=1)
        if case == "inactive_lane":
            bt = jnp.zeros_like(bt)
        outs = []
        for null in (0.0, 3e4):
            poisoned = [p.at[0].set(null) for p in (kp, vp)]
            outs.append(np.asarray(
                paged_read("tiled", q, *poisoned, bt, b, window)))
            assert np.isfinite(outs[-1]).all()
        if case != "inactive_lane":
            np.testing.assert_array_equal(
                outs[0][:, :real], outs[1][:, :real])
            ref = paged_read("gather", q, kp, vp, bt, b, window)
            np.testing.assert_allclose(
                outs[1][:, :real], np.asarray(ref)[:, :real],
                atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kind", ["full", "windowed"])
    def test_bf16_sits_as_near_a_float32_reference_as_the_gather(
            self, kind):
        """bf16 query and cache: the operands go to both products as
        they are, so the kernel and the gather (float32 products on the
        CPU) part by bf16 rounding of the weights; each is held to the
        float32 reference of the same values, as the tick's kernel is."""
        window = self.WINDOW if kind == "windowed" else 0
        geo = self._geometry(8, 4, 150, window, seed=3,
                             dtype=jnp.bfloat16)
        out, ref = self._both(*geo, window)
        assert out.dtype == jnp.bfloat16
        exact = self._both(
            *(a.astype(jnp.float32) for a in geo[:3]), *geo[3:], window)[1]
        err = lambda a: float(jnp.sqrt(jnp.mean(  # noqa: E731
            (a.astype(jnp.float32) - exact) ** 2)))
        scale = float(jnp.sqrt(jnp.mean(exact ** 2)))
        assert err(ref) < 0.01 * scale
        assert err(out) < 0.01 * scale

    def test_only_the_tiles_a_window_sees_are_copied(self):
        """The key axis' block index, as the pipeline sees it over a
        query tile's sweep: clamped into `_tile_span`, so a step outside
        the span names the block the step before it held and starts no
        copy. A 40-position prompt at 150 in a view of 448 keys: tile
        1 only for a full layer's first query tile; a windowed layer
        (160) starts where its first query's window does."""
        import hyperion_tpu.ops.pallas.window_attention as wa

        def sweep(base, view0, qi, window):
            lo, hi = wa._tile_span(jnp.int32(base), jnp.int32(view0), qi,
                                   tq=16, tk=128, nk=4, window=window)
            return [int(jnp.clip(ki, lo, hi)) for ki in range(4)]

        assert sweep(150, 0, 0, 0) == [0, 1, 1, 1]
        assert sweep(0, 0, 0, 0) == [0, 0, 0, 0]
        assert sweep(300, 0, 2, 0) == [0, 1, 2, 2]
        assert sweep(300, 0, 0, 160) == [1, 1, 2, 2]
        # a view that starts at the window's block: position 140
        assert sweep(300, 140, 0, 160) == [0, 1, 1, 1]
        # bucket padding beyond the view's end stays inside it
        assert sweep(440, 0, 2, 0) == [0, 1, 2, 3]

    @pytest.mark.parametrize("tiles", [(16, 128), (8, 256), (64, 512)])
    def test_tiles_given_by_the_caller(self, tiles):
        """The kernel itself over a view the caller gathered, with the
        tiles handed in (`tile_q`, `tile_k`: what a probe sweeps): the
        same answer at any tiling, a window that is no multiple of the
        query tile included."""
        from hyperion_tpu.models.llama import paged_read
        from hyperion_tpu.ops.pallas.window_attention import (
            window_attention,
        )

        q, kp, vp, bt, base = self._geometry(2, 4, 150, 0, seed=4)
        bt = jnp.pad(bt, ((0, 0), (0, 16)), mode="edge")   # 512 keys
        out = window_attention(
            q, kp[bt], vp[bt], base, jnp.zeros_like(base),
            keys=self.MB * self.bs, tile_q=tiles[0], tile_k=tiles[1])
        ref = paged_read("gather", q, kp, vp, bt[:, :self.MB], base)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_refuses_a_view_that_is_not_whole_tiles(self):
        from hyperion_tpu.ops.pallas.window_attention import (
            window_attention,
        )

        q = jnp.zeros((1, 16, 4, 16))
        kv = jnp.zeros((1, 40, 2, 4, 16))      # 160 keys, tiles of 128
        zero = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="whole tiles"):
            window_attention(q, kv, kv, zero, zero)

    def test_plan_follows_the_shape_and_the_budget(self, monkeypatch):
        import hyperion_tpu.ops.pallas.window_attention as wa

        monkeypatch.undo()
        for T, rep, blocks in [(2048, 4, 128), (512, 6, 289),
                               (512, 6, 768), (512, 7, 289),
                               (512, 1, 48), (64, 4, 128), (8, 4, 128)]:
            tq, tk = wa._plan(T, rep, 128, 16, blocks, jnp.bfloat16,
                              jnp.bfloat16)
            assert tq & (tq - 1) == 0 and 16 <= tq <= max(16, T)
            assert tk % 128 == 0 and tk % 16 == 0
            assert tk <= -(-blocks * 16 // 128) * 128
            assert wa.plan_vmem_bytes(tq, tk, rep, 128, 2, 2) \
                <= wa._VMEM_BUDGET < wa._VMEM_LIMIT
        # float32 operands at wide heads: the step is cut, not refused
        tq, tk = wa._plan(4096, 8, 256, 16, 1024, jnp.float32, jnp.float32)
        assert wa.plan_vmem_bytes(tq, tk, 8, 256, 4, 4) <= wa._VMEM_BUDGET

    def test_model_level_matches_gather(self):
        """Llama tiny (GQA rep 2) with every paged read through the
        tiled kernel against the gather, caches threaded forward per
        impl: a prefill of a bucket longer than its prompt, a chunk at
        a mid-block base, a verify window and a decode row. Logits and
        caches within the pinned float32 bound at every step."""
        import dataclasses

        from hyperion_tpu.models.llama import (
            Llama, init_paged_cache, llama_tiny_config)

        cfg = llama_tiny_config(n_kv_heads=2, max_len=48)
        bs, B = 4, 2
        MB = cfg.max_len // bs
        models = {"gather": Llama(cfg), "tiled": Llama(
            dataclasses.replace(cfg, paged_attn_impl="tiled"))}
        params = models["gather"].init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
        caches = {n: init_paged_cache(cfg, B * MB + 1, bs) for n in models}
        rng = np.random.default_rng(0)
        bt = jnp.asarray(rng.permutation(np.arange(1, B * MB + 1))
                         .reshape(B, MB).astype(np.int32))

        def step(ids, index, tables, real=None):
            outs = {}
            for name, model in models.items():
                outs[name], caches[name] = model.apply(
                    {"params": params}, ids, cache=caches[name],
                    cache_index=index, block_tables=tables)
            np.testing.assert_allclose(
                np.asarray(outs["tiled"])[:, :real],
                np.asarray(outs["gather"])[:, :real],
                atol=2e-5, rtol=2e-5)
            for lg, lt in zip(caches["gather"], caches["tiled"]):
                for kv in ("k", "v"):
                    # block 0 holds what the bucket's padding wrote
                    np.testing.assert_allclose(
                        np.asarray(lg[kv])[1:], np.asarray(lt[kv])[1:],
                        atol=2e-5, rtol=2e-5)

        def ids(*shape):
            return jnp.asarray(rng.integers(0, cfg.vocab_size, shape),
                               jnp.int32)

        # a prompt of 11 in a bucket of 16: its table covers 3 blocks
        step(ids(1, 16), 0, bt[:1].at[:, 3:].set(0), real=11)
        step(ids(1, 16), 11, bt[:1])                     # a chunk at 11
        step(ids(B, 3), jnp.asarray([27, 0], jnp.int32), bt)   # [S, k+1]
        step(ids(B, 1), jnp.asarray([30, 3], jnp.int32), bt)   # [S, 1]


class TestGroupedMatmul:
    """`ops/pallas/grouped_matmul.py` through the interpreter against
    `lax.ragged_dot`, on the rows that belong to a group: what lies
    past `sum(sizes)` holds no number in either."""

    @staticmethod
    def operands(M, G, K, N, seed=0, dtype=jnp.float32):
        k = jax.random.split(jax.random.key(seed), 2)
        return (jax.random.normal(k[0], (M, K), dtype),
                jax.random.normal(k[1], (G, K, N), dtype) / 8)

    @classmethod
    def agree(cls, M, G, K, N, sizes, tiling, dtype=jnp.float32, tol=2e-5):
        from hyperion_tpu.ops.pallas.grouped_matmul import grouped_matmul

        lhs, rhs = cls.operands(M, G, K, N, dtype=dtype)
        sizes = jnp.asarray(sizes, jnp.int32)
        want = jax.lax.ragged_dot(lhs, rhs, sizes)
        got = grouped_matmul(lhs, rhs, sizes, tiling=tiling)
        assert got.shape == want.shape and got.dtype == want.dtype
        n = int(sizes.sum())
        np.testing.assert_allclose(
            np.asarray(got[:n], np.float32), np.asarray(want[:n], np.float32),
            atol=tol, rtol=tol)
        assert float(jnp.abs(want[:n].astype(jnp.float32)).max()) > 0.5
        return got

    @pytest.mark.parametrize("sizes", [
        pytest.param([10, 20, 30, 4], id="every_group_touched"),
        pytest.param([0, 0, 40, 24], id="none_at_the_start"),
        pytest.param([12, 0, 0, 52], id="none_in_the_middle"),
        pytest.param([33, 31, 0, 0], id="none_at_the_end"),
        pytest.param([0, 64, 0, 0], id="all_rows_in_one_group"),
        pytest.param([0, 0, 0, 64], id="all_rows_in_the_last_group"),
        pytest.param([16, 16, 16, 16], id="groups_end_on_tile_edges"),
        pytest.param([1, 1, 1, 1], id="one_row_a_group"),
    ])
    @pytest.mark.parametrize("tm", [16, 32])
    def test_groups(self, sizes, tm):
        self.agree(64, 4, 128, 256, sizes, (tm, 256))

    @pytest.mark.parametrize("sizes, M", [
        pytest.param([3, 0, 5, 0, 7, 1, 0, 0], 64, id="a_few_rows_held"),
        pytest.param([0, 0, 0, 0, 0, 0, 0, 3], 64, id="only_the_last_group"),
        pytest.param([16, 0, 0, 0, 0, 0, 0, 0], 48, id="one_whole_tile"),
        pytest.param([0, 0, 0, 0, 0, 0, 0, 0], 32, id="no_row_at_all"),
    ])
    def test_rows_past_the_groups_are_never_visited(self, sizes, M):
        """A held share whose rows for absent experts sort last: the
        walk ends at `sum(sizes)`; tiles past it are neither read nor
        written (their visits are not listed)."""
        from hyperion_tpu.ops.pallas.grouped_matmul import group_visits

        v = group_visits(jnp.asarray(sizes, jnp.int32), M, 16)
        n = sum(sizes)
        touched = sum(s > 0 for s in sizes)
        # a group's visits: the tiles its rows lie in
        ends = np.cumsum(sizes)
        want = sum(-(-e // 16) - (e - s) // 16 for s, e in
                   zip(sizes, ends) if s) if n else 0
        assert int(v.count[0]) == want >= touched
        ids = np.asarray(v.tile_ids)[:want]
        assert (np.diff(ids) >= 0).all() and (ids <= max(n - 1, 0) // 16).all()
        if n:
            self.agree(M, 8, 256, 128, sizes, (16, 128))

    @pytest.mark.parametrize("M", [16, 32, 48, 50, 17])
    def test_rows_at_the_row_tiles_edge(self, M):
        """M one tile, whole tiles, and not a multiple (padded up)."""
        sizes = [M // 3, 0, M - M // 3 - M // 4, M // 4]
        self.agree(M, 4, 128, 128, sizes, (16, 128))
        self.agree(M, 4, 128, 128, sizes, (32, 128))

    @pytest.mark.parametrize("K, N, tn", [
        pytest.param(256, 128, 128, id="gate_up_orientation"),
        pytest.param(128, 256, 256, id="down_orientation"),
        pytest.param(128, 384, 128, id="columns_cut_in_three"),
    ])
    def test_orientations_and_column_tiles(self, K, N, tn):
        self.agree(96, 6, K, N, [20, 0, 31, 9, 30, 6], (32, tn))

    def test_bf16_operands_float32_accumulation(self):
        """bf16 in, bf16 out, the sum in float32: within a bf16 step of
        `ragged_dot`'s, and far from a bf16 accumulation's."""
        got = self.agree(64, 4, 512, 128, [10, 20, 30, 4], (16, 128),
                         dtype=jnp.bfloat16, tol=1e-2)
        assert got.dtype == jnp.bfloat16

    def test_one_walk_serves_the_three_products(self):
        """`group_visits` once, shared by products over the same rows."""
        from hyperion_tpu.ops.pallas.grouped_matmul import (
            group_visits,
            grouped_matmul,
        )

        lhs, rhs = self.operands(64, 4, 128, 256)
        down = jnp.swapaxes(rhs, 1, 2)
        sizes = jnp.asarray([5, 0, 40, 11], jnp.int32)
        v = group_visits(sizes, 64, 16)
        a = grouped_matmul(lhs, rhs, sizes, visits=v)
        b = grouped_matmul(a, down, sizes, visits=v)
        want = jax.lax.ragged_dot(
            jax.lax.ragged_dot(lhs, rhs, sizes), down, sizes)
        np.testing.assert_allclose(np.asarray(b[:56]), np.asarray(want[:56]),
                                   atol=1e-3, rtol=1e-4)

    @pytest.mark.parametrize("bad", ["rows", "columns", "sizes", "visits"])
    def test_refuses_what_it_cannot_cut(self, bad):
        from hyperion_tpu.ops.pallas.grouped_matmul import (
            group_visits,
            grouped_matmul,
        )

        lhs, rhs = self.operands(64, 4, 128, 256)
        sizes = jnp.asarray([16, 16, 16, 16], jnp.int32)
        with pytest.raises(ValueError):
            if bad == "rows":
                grouped_matmul(lhs, rhs, sizes, tiling=(24, 256))
            elif bad == "columns":
                grouped_matmul(lhs, rhs, sizes, tiling=(16, 96))
            elif bad == "sizes":
                grouped_matmul(lhs, rhs, sizes[:3])
            else:
                grouped_matmul(lhs, rhs, sizes,
                               visits=group_visits(sizes, 32, 16))

    def test_plan_tiles(self):
        """A whole matrix a copy where two fit the budget; the widest
        column tile that divides N and fits, where they do not."""
        from hyperion_tpu.ops.pallas.grouped_matmul import plan_tiles

        assert plan_tiles(288, 2560, 768, 2)[1] == 768      # 3.9 MB
        assert plan_tiles(288, 768, 2560, 2)[1] == 2560
        assert plan_tiles(96, 3072, 3072, 2)[1] == 1024     # 18.9 MB
