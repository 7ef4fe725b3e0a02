"""Speculative decoding (`infer/speculative.py`).

The load-bearing property: greedy speculative output is token-for-token
IDENTICAL to plain greedy KV-cache decoding with the target alone, for
any draft model — good, bad, or the target itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.infer.generate import generate
from hyperion_tpu.infer.speculative import accept_draft, generate_speculative
from hyperion_tpu.models.llama import Llama, llama_tiny_config


def _model(seed: int, **kw):
    cfg = llama_tiny_config(**kw)
    model = Llama(cfg)
    params = model.init_params(jax.random.key(seed), batch=1, seq=8)
    return model, {"params": params}


@pytest.fixture(scope="module")
def target():
    return _model(0)


@pytest.fixture(scope="module")
def prompt():
    return jax.random.randint(jax.random.key(7), (1, 8), 1, 250, jnp.int32)


class TestEqualsGreedy:
    def _check(self, target, draft, prompt, n=12, k=4):
        model, variables = target
        dmodel, dvariables = draft
        ref = generate(model, variables, prompt, n)
        out = generate_speculative(
            model, variables, dmodel, dvariables, prompt, n, k=k
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_random_draft(self, target, prompt):
        # an unrelated draft: most proposals rejected, output unchanged
        self._check(target, _model(1), prompt)

    def test_draft_is_target(self, target, prompt):
        # perfect draft: every round fully accepts (exercises the
        # bonus-token path and the draft window re-feed after it)
        self._check(target, target, prompt)

    def test_smaller_draft_architecture(self, target, prompt):
        draft = _model(2, n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                       ff_dim=64)
        self._check(target, draft, prompt)

    def test_k_one(self, target, prompt):
        self._check(target, target, prompt, k=1)

    def test_k_larger_than_needed(self, target, prompt):
        self._check(target, target, prompt, n=3, k=6)

    def test_eos_masking_matches(self, target, prompt):
        model, variables = target
        # force an eos the model actually emits: take the 3rd greedy
        # token as the "eos" id so masking kicks in mid-sequence
        ref = generate(model, variables, prompt, 10)
        eos = int(np.asarray(ref)[0, 2])
        ref_eos = generate(model, variables, prompt, 10, eos_id=eos,
                           pad_id=0)
        out = generate_speculative(
            model, variables, model, variables, prompt, 10, k=3,
            eos_id=eos, pad_id=0,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_eos))


class TestAcceptDraft:
    """The shared acceptance rule (`accept_draft`) — also the serve
    engine's verify step, so its contract is pinned here directly."""

    def test_partial_prefix_takes_correction(self):
        m, v = accept_draft(jnp.array([[5, 6, 7]]),
                            jnp.array([[5, 6, 9, 8]]))
        assert int(m[0]) == 2
        # accepted tokens are v[:m+1]: the agreeing prefix plus the
        # target's correction at the first disagreement
        np.testing.assert_array_equal(np.asarray(v)[0, :3], [5, 6, 9])

    def test_full_accept_takes_bonus(self):
        m, v = accept_draft(jnp.array([[5, 6, 7]]),
                            jnp.array([[5, 6, 7, 8]]))
        assert int(m[0]) == 3
        np.testing.assert_array_equal(np.asarray(v)[0], [5, 6, 7, 8])

    def test_immediate_miss(self):
        m, v = accept_draft(jnp.array([[9, 9]]), jnp.array([[5, 6, 7]]))
        assert int(m[0]) == 0
        assert int(np.asarray(v)[0, 0]) == 5

    def test_batched_rows_independent(self):
        draft = jnp.array([[5, 6], [1, 2]])
        target = jnp.array([[5, 6, 7], [3, 4, 5]])
        m, _ = accept_draft(draft, target)
        np.testing.assert_array_equal(np.asarray(m), [2, 0])


class TestBatched:
    """Batch lifting (PR 12): rows are independent vmapped lanes, and
    the batch-1 call bypasses vmap entirely so the original
    single-sequence output stays byte-identical."""

    def test_batched_rows_equal_greedy_and_solo(self, target):
        # one batched trace covers both pins: every row equals plain
        # greedy decoding, and row 0 equals the batch-1 (vmap-bypassed)
        # call — so batching changed scheduling, not numerics
        model, variables = target
        prompts = jax.random.randint(
            jax.random.key(11), (2, 8), 1, 250, jnp.int32)
        out = generate_speculative(
            model, variables, model, variables, prompts, 10, k=3)
        ref = generate(model, variables, prompts, 10)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        solo = generate_speculative(
            model, variables, model, variables, prompts[:1], 10, k=3)
        np.testing.assert_array_equal(
            np.asarray(out)[0], np.asarray(solo)[0])


class TestValidation:
    def test_empty_batch_rejected(self, target):
        model, variables = target
        ids = jnp.ones((0, 8), jnp.int32)
        with pytest.raises(ValueError, match="at least one row"):
            generate_speculative(model, variables, model, variables, ids, 4)

    def test_prompt_longer_than_k(self, target):
        model, variables = target
        ids = jnp.ones((1, 3), jnp.int32)
        with pytest.raises(ValueError, match="must exceed k"):
            generate_speculative(model, variables, model, variables, ids, 4,
                                 k=4)

    def test_vocab_mismatch(self, target, prompt):
        model, variables = target
        draft, dvars = _model(3, vocab_size=128)
        with pytest.raises(ValueError, match="vocab mismatch"):
            generate_speculative(model, variables, draft, dvars, prompt, 4)

    def test_length_guard(self, target, prompt):
        model, variables = target
        with pytest.raises(ValueError, match="exceeds max_len"):
            generate_speculative(model, variables, model, variables,
                                 prompt, 10_000)


class TestBreakevenAcceptance:
    """spec_breakeven_acceptance — the pure cost model the speculative
    pairing analysis uses (decode_bench.spec_breakeven_acceptance)."""

    def test_free_draft_needs_nothing(self):
        from hyperion_tpu.bench.decode_bench import spec_breakeven_acceptance

        # a zero-cost draft: any acceptance that yields >1 token/round
        # wins; breakeven is exactly "rounds emit 1 token" -> p=0
        assert spec_breakeven_acceptance(0.0, 10.0, k=4) == 0.0

    def test_equal_cost_draft_cannot_win(self):
        from hyperion_tpu.bench.decode_bench import spec_breakeven_acceptance

        # k drafts as expensive as the target: round costs (k+1)x, max
        # emission is k+1 tokens — total acceptance exactly TIES, which
        # does not beat plain decode, so the verdict is inf
        assert spec_breakeven_acceptance(10.0, 10.0, k=4) == float("inf")

    def test_overpriced_draft_is_inf(self):
        from hyperion_tpu.bench.decode_bench import spec_breakeven_acceptance

        assert spec_breakeven_acceptance(20.0, 10.0, k=4) == float("inf")

    def test_cheap_draft_breakeven_is_moderate(self):
        from hyperion_tpu.bench.decode_bench import spec_breakeven_acceptance

        # 10x-cheaper draft, k=4: round costs 1.4 target-forwards, so
        # E[tokens] must reach 1.4 -> p around 0.3-0.5
        p = spec_breakeven_acceptance(1.0, 10.0, k=4)
        assert 0.2 < p < 0.6
        # and the model is monotone: cheaper drafts need less agreement
        assert spec_breakeven_acceptance(0.5, 10.0, k=4) < p
