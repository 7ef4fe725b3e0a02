"""`ops/pallas/state_update.py`: the gated delta rule's one-position
update over the rows a decode tick advances, through the Pallas
interpreter, against `models/solar_open2.py` `delta_rule_step` (float32,
elementwise: the same equations). The advanced rows agree to float32
round-off; every other row of S leaves the call bit for bit as it
entered. What the interpreter cannot show, the write-back of aliased
blocks on the chip, is a chip probe's (PERF.md section 6)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.models.solar_open2 import delta_rule_step
from hyperion_tpu.ops.pallas import state_update

B = 8
MASKS = {
    "none": np.zeros(B, bool),
    "one": np.eye(B, dtype=bool)[3],
    "all": np.ones(B, bool),
    "scattered": np.array([1, 0, 0, 1, 1, 0, 1, 0], bool),
    "first": np.eye(B, dtype=bool)[0],
    "last": np.eye(B, dtype=bool)[B - 1],
}
# heads: one block of every head (4, and 12: more than a sublane tile,
# which no tile divides), and the serving cell's 64 in two blocks of 32
# (a grid of two head blocks)
SHAPES = {"one_block": 4, "one_block_of_12": 12, "two_blocks": 64}


def inputs(H, K=128, seed=0):
    """A tick's operands as the KDA layer makes them: q and k unit
    vectors (q scaled), log-decays weak to strong, beta in (0, 2), a
    state of unit scale."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, H, K))) / np.sqrt(K)
    k = unit(jax.random.normal(ks[1], (B, H, K)))
    v = jax.random.normal(ks[2], (B, H, K))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, H, K), minval=-4, maxval=1))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    S = jax.random.normal(ks[5], (B, H, K, K))
    return q, k, v, g, beta, S


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_the_advanced_rows_agree_and_the_others_keep_every_bit(mask, shape):
    H = SHAPES[shape]
    q, k, v, g, beta, S = inputs(H)
    adv = MASKS[mask]
    S_before = np.asarray(S)
    o, S1 = jax.jit(state_update.delta_rule_step_rows)(
        q, k, v, g, beta, S, jnp.asarray(adv))
    o_want, S_want = jax.jit(delta_rule_step)(q, k, v, g, beta, S)
    o, S1 = np.asarray(o), np.asarray(S1)
    assert o.shape == (B, H, 128) and S1.shape == S_before.shape
    # rows not advanced (with none advanced, the whole array): bit for bit
    np.testing.assert_array_equal(S1[~adv], S_before[~adv])
    if adv.any():
        # float32 round-off of sums over 128 channels
        np.testing.assert_allclose(o[adv], np.asarray(o_want)[adv],
                                   rtol=0, atol=2e-6)
        np.testing.assert_allclose(S1[adv], np.asarray(S_want)[adv],
                                   rtol=0, atol=1e-5)
        assert not np.array_equal(S1[adv], S_before[adv])


def test_the_head_block_fits_its_budget_in_whole_sublane_tiles():
    # the serving cell's tick: 64 heads of 128, two blocks of 2 MiB
    assert state_update.head_block(64, 128, 128) == 32
    # a head count that no sublane tile divides takes every head
    assert state_update.head_block(4, 128, 128) == 4
    assert state_update.head_block(2, 8, 8) == 2
    # no block fits the budget: the fewest heads a block may take
    assert state_update.head_block(16, 256, 512) == 8
    with pytest.raises(ValueError, match="float32"):
        q, k, v, g, beta, S = inputs(4)
        state_update.delta_rule_step_rows(
            q, k, v, g, beta, S.astype(jnp.bfloat16), jnp.ones(B, bool))
