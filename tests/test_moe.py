"""MoE routing, dispatch algebra, expert parallelism, and the MoE LM.
Runs on the simulated 8-device CPU mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.models.moe_lm import MoELM, MoELMConfig
from hyperion_tpu.models.transformer_lm import simple_lm_config
from hyperion_tpu.ops.moe import (
    MoEConfig, init_moe_params, moe_ffn, top_k_routing,
)
from hyperion_tpu.runtime.mesh import (
    AxisName, MeshSpec, activate_mesh, make_mesh,
)

D = 16


def moe_cfg(**kw):
    base = dict(n_experts=4, top_k=2, capacity_factor=2.0, d_model=D,
                ff_dim=32)
    base.update(kw)
    return MoEConfig(**base)


class TestRouting:
    def test_dispatch_combine_shapes_and_mass(self):
        cfg = moe_cfg()
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(0), (24, cfg.n_experts)), -1
        )
        C = cfg.capacity(24)
        dispatch, combine = top_k_routing(probs, cfg, C)
        assert dispatch.shape == (24, cfg.n_experts, C)
        # every token occupies exactly top_k slots (capacity is ample)
        np.testing.assert_allclose(
            np.asarray(dispatch.sum(axis=(1, 2))), cfg.top_k, atol=1e-6
        )
        # combine weights renormalize to 1 per token
        np.testing.assert_allclose(
            np.asarray(combine.sum(axis=(1, 2))), 1.0, atol=1e-5
        )
        # no expert slot double-booked
        assert float(dispatch.sum(axis=0).max()) <= 1.0 + 1e-6

    def test_capacity_drops_overflow(self):
        cfg = moe_cfg(top_k=1, capacity_factor=1.0)
        # all tokens want expert 0 → only `capacity` survive
        probs = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (16, 1))
        C = 2
        dispatch, combine = top_k_routing(probs, cfg, C)
        assert float(dispatch.sum()) == C  # exactly capacity kept
        assert float(combine[C:].sum()) == 0.0  # later tokens dropped

    def test_top1_vs_top2_gate_normalization(self):
        cfg1, cfg2 = moe_cfg(top_k=1), moe_cfg(top_k=2)
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(1), (12, 4)), -1
        )
        _, c1 = top_k_routing(probs, cfg1, 12)
        _, c2 = top_k_routing(probs, cfg2, 12)
        np.testing.assert_allclose(np.asarray(c1.sum((1, 2))), 1.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(c2.sum((1, 2))), 1.0, atol=1e-5)


class TestRoutingValidation:
    def test_top_k_exceeding_experts_raises(self):
        with pytest.raises(ValueError, match="top_k"):
            moe_cfg(n_experts=1, top_k=2)

    def test_padding_consumes_no_capacity(self):
        """Pads must not steal slots: with capacity exactly the real
        count, every real token survives when pads are masked out."""
        cfg = moe_cfg(top_k=1, capacity_factor=1.0)
        N = 16
        # everyone wants expert 0; first half of tokens are padding
        probs = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (N, 1))
        valid = jnp.concatenate([jnp.zeros(8), jnp.ones(8)])
        dispatch, combine = top_k_routing(probs, cfg, 8, valid)
        # all 8 real tokens kept (pads would have filled the slots)
        assert float(dispatch[8:].sum()) == 8.0
        # pads dispatched nowhere, zero combine weight
        assert float(dispatch[:8].sum()) == 0.0
        assert float(combine[:8].sum()) == 0.0


class TestMoEFFN:
    def test_output_finite_and_shaped(self):
        cfg = moe_cfg()
        params = init_moe_params(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (2, 8, D), jnp.float32)
        y, aux = moe_ffn(params, x, cfg)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert np.isfinite(float(aux))

    def test_aux_loss_balanced_near_one(self):
        """Uniform routing ⇒ GShard aux ≈ 1; collapsed routing ⇒ ≈ E."""
        cfg = moe_cfg(top_k=1)
        E = cfg.n_experts
        N = 64
        uniform = jnp.full((N, E), 1.0 / E)
        # break argmax ties round-robin to emulate balanced top-1 counts
        uniform = uniform + jax.nn.one_hot(jnp.arange(N) % E, E) * 1e-6
        top1 = jax.nn.one_hot(jnp.argmax(uniform, -1), E)
        aux_u = E * float(jnp.sum(top1.mean(0) * uniform.mean(0)))
        assert abs(aux_u - 1.0) < 1e-3
        collapsed = jax.nn.one_hot(jnp.zeros(N, jnp.int32), E) * 0.99 + 0.0025
        top1c = jax.nn.one_hot(jnp.argmax(collapsed, -1), E)
        aux_c = E * float(jnp.sum(top1c.mean(0) * collapsed.mean(0)))
        assert aux_c > 3.0

    def test_expert_parallel_matches_unsharded(self):
        """The expert-sharded run is GSPMD layout only — outputs must
        match the meshless run exactly (up to fp tolerance)."""
        cfg = moe_cfg()
        params = init_moe_params(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (2, 16, D), jnp.float32)
        ref, aux_ref = moe_ffn(params, x, cfg)
        mesh = make_mesh(MeshSpec(data=2, expert=4))
        with activate_mesh(mesh):
            out, aux = jax.jit(lambda p, x: moe_ffn(p, x, cfg))(params, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )
        assert abs(float(aux) - float(aux_ref)) < 1e-5

    def test_padded_tokens_pass_through_as_zero(self):
        cfg = moe_cfg()
        params = init_moe_params(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (2, 8, D), jnp.float32)
        mask = np.ones((2, 8), np.int8)
        mask[:, 6:] = 0
        y, aux = moe_ffn(params, x, cfg, padding_mask=jnp.asarray(mask))
        # pad positions produce exactly zero (residual carries them)
        assert float(jnp.abs(y[:, 6:]).max()) == 0.0
        assert float(jnp.abs(y[:, :6]).max()) > 0.0
        assert np.isfinite(float(aux))

    def test_grouping_keeps_dispatch_linear(self):
        """Dispatch memory per group is [g, E, C(g)]: doubling the batch
        doubles G, not C — total stays linear in tokens."""
        cfg = moe_cfg()
        # capacity is a function of GROUP size, linear in it — not of
        # the total batch token count
        assert cfg.capacity(16) == 2 * cfg.capacity(8)
        p1 = init_moe_params(jax.random.key(0), cfg)
        x1 = jax.random.normal(jax.random.key(1), (1, 8, D), jnp.float32)
        x2 = jnp.concatenate([x1, x1], axis=0)  # two identical rows
        y1, _ = moe_ffn(p1, x1, cfg)
        y2, _ = moe_ffn(p1, x2, cfg)
        # per-row grouping ⇒ each row routes independently: identical
        # rows give identical outputs regardless of batch size
        np.testing.assert_allclose(
            np.asarray(y2[0]), np.asarray(y1[0]), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(y2[1]), np.asarray(y1[0]), atol=1e-6
        )

    @pytest.mark.slow
    def test_grads_flow_to_all_experts(self):
        cfg = moe_cfg(capacity_factor=4.0)
        params = init_moe_params(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(2), (4, 16, D), jnp.float32)

        def loss(p):
            y, aux = moe_ffn(p, x, cfg)
            return jnp.mean(y**2) + 0.01 * aux

        g = jax.grad(loss)(params)
        # with 64 tokens over 4 experts every expert sees traffic
        per_expert = np.asarray(jnp.abs(g["experts"]["wi"]).sum(axis=(1, 2)))
        assert (per_expert > 0).all(), per_expert
        assert np.abs(np.asarray(g["router"]["kernel"])).sum() > 0


class TestMoELM:
    def _model(self):
        base = simple_lm_config(
            vocab_size=64, d_model=D, n_heads=4, n_layers=2, ff_dim=32,
            max_len=8, dropout=0.0,
        )
        return MoELM(MoELMConfig(base=base, moe=moe_cfg(), moe_every=2))

    def test_forward_and_aux(self):
        model = self._model()
        params = model.init_params(jax.random.key(0))
        ids = jnp.zeros((2, 8), jnp.int32)
        logits, aux = model.apply_with_aux({"params": params}, ids)
        assert logits.shape == (2, 8, 64)
        assert logits.dtype == jnp.float32
        assert float(aux) > 0  # one MoE layer sowed its loss

    @pytest.mark.slow
    def test_remat_matches_and_grads(self):
        """cfg.base.remat must reach both dense and sparse blocks (the
        TransformerLM scaffold is shared; regression for the dropped
        wrapping)."""
        import dataclasses as dc

        model = self._model()
        params = model.init_params(jax.random.key(0))
        cfg_r = dc.replace(
            model.cfg, base=dc.replace(model.cfg.base, remat="full")
        )
        model_r = MoELM(cfg_r)
        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (2, 8)), jnp.int32
        )

        def loss(m, p):
            logits, aux = m.apply_with_aux({"params": p}, ids)
            return jnp.mean(logits**2) + aux

        g = jax.grad(lambda p: loss(model, p))(params)
        g_r = jax.grad(lambda p: loss(model_r, p))(params)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_r)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
            )

    def test_expert_leaves_get_expert_axis(self):
        from flax import traverse_util
        from jax.sharding import PartitionSpec

        from hyperion_tpu.parallel.partition import partition_specs

        model = self._model()
        params = jax.eval_shape(
            lambda r: model.init_params(r), jax.random.key(0)
        )
        mesh = make_mesh(MeshSpec(data=2, expert=4))
        specs = traverse_util.flatten_dict(
            partition_specs(params, mesh, fsdp=False), sep="/",
            is_leaf=lambda _, v: isinstance(v, PartitionSpec),
        )
        expert_specs = {k: v for k, v in specs.items() if "/experts/" in k}
        assert expert_specs
        for k, v in expert_specs.items():
            assert v and v[0] == AxisName.EXPERT, (k, v)

    @pytest.mark.slow
    def test_train_step_decreases_loss(self):
        import optax

        from hyperion_tpu.runtime.mesh import batch_sharding
        from hyperion_tpu.train import (
            create_train_state, make_optimizer, make_train_step,
            next_token_loss,
        )

        model = self._model()
        mesh = make_mesh(MeshSpec(data=2, expert=4))
        opt = make_optimizer(1e-2)
        with activate_mesh(mesh):
            state, sharding = create_train_state(
                lambda r: {"params": model.init_params(r)}, opt, mesh,
                jax.random.key(0), policy="fp32", fsdp=False,
            )

            def loss_fn(params, batch_stats, batch, rngs):
                logits, aux = model.apply_with_aux(
                    {"params": params}, batch["input_ids"],
                    padding_mask=batch["attention_mask"],
                )
                loss = next_token_loss(
                    logits, batch["input_ids"], batch["attention_mask"]
                ) + aux
                return loss, ({"loss": loss}, batch_stats)

            step = make_train_step(loss_fn, opt, sharding)
            ids = np.random.default_rng(0).integers(0, 64, (8, 8))
            sh = batch_sharding(mesh)
            batch = {
                "input_ids": jax.device_put(ids.astype(np.int32), sh),
                "attention_mask": jax.device_put(np.ones((8, 8), np.int8), sh),
            }
            losses = []
            rng = jax.random.key(1)
            for i in range(5):
                state, metrics = step(state, batch, rng)
                losses.append(float(metrics["loss"]))
            assert losses[-1] < losses[0], losses


# ------------- serving: the expert step's two forms (ops/moe.py)


def _expert_layer(experts=8, d=32, f=16, tokens=20, top_k=3, seed=0):
    from hyperion_tpu.ops import moe

    k = jax.random.split(jax.random.key(seed), 5)
    p = {"gate": jax.random.normal(k[0], (experts, d, f)) / 4,
         "up": jax.random.normal(k[1], (experts, d, f)) / 4,
         "down": jax.random.normal(k[2], (experts, f, d)) / 4}
    x = jax.random.normal(k[3], (tokens, d))
    picked, w = moe.softmax_topk_route(
        x, jax.random.normal(k[4], (d, experts)), top_k=top_k)
    return p, x, picked, w


def _share(p, first, count):
    return {k: v[first:first + count] for k, v in p.items()}


class _Forced:
    """`select_grouped_impl` answering "kernel" for every shape, as it
    does on a TPU for the shapes of its rule: the kernel runs through
    the interpreter here. No option of the program is involved: there
    is none."""

    def __enter__(self):
        from hyperion_tpu.ops import moe

        self.mp = pytest.MonkeyPatch()
        self.asked = []

        def forced(rows, groups, k, n, backend, itemsize=2):
            self.asked.append((rows, groups, k, n, backend, itemsize))
            return "kernel"

        self.mp.setattr(moe, "select_grouped_impl", forced)
        return self

    def __exit__(self, *exc):
        self.mp.undo()


class TestGroupedExpertsForms:
    @pytest.mark.parametrize("act", [jax.nn.silu, jax.nn.relu],
                             ids=["swiglu", "relu_gate"])
    @pytest.mark.parametrize("held", [(0, 8), (2, 4), (6, 2)],
                             ids=["every_expert", "a_middle_share",
                                  "the_last_two"])
    def test_the_kernel_gives_what_ragged_dot_gives(self, act, held):
        """`grouped_experts` whole, both forms: the same result to
        float32 round-off and the same `load`, for a share whose rows
        for absent experts lie past `sum(sizes)` too (what the kernel
        leaves there is selected around, never read)."""
        from hyperion_tpu.ops import moe

        p, x, picked, w = _expert_layer()
        p = _share(p, *held)
        want, load = moe.grouped_experts(x, picked, w, p, held=held, act=act)
        with _Forced() as sel:
            got, load_k = moe.grouped_experts(
                x, picked, w, p, held=held, act=act)
        # asked once a call, with the call's own static shape
        assert sel.asked == [(20 * 3, held[1], 32, 16, "cpu", 4)]
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert bool((load == load_k).all())
        assert float(jnp.abs(want).max()) > 0.05

    def test_the_shares_add_up_to_the_whole_through_the_kernel(self):
        from hyperion_tpu.ops import moe

        p, x, picked, w = _expert_layer(tokens=24)
        whole, _ = moe.grouped_experts(x, picked, w, p, held=(0, 8))
        with _Forced():
            parts = [moe.grouped_experts(
                x, picked, w, _share(p, first, 2), held=(first, 2))
                for first in (0, 2, 4, 6)]
        np.testing.assert_allclose(
            np.asarray(sum(y for y, _ in parts)), np.asarray(whole),
            atol=1e-5, rtol=1e-5)
        assert sum(int(ld.sum()) for _, ld in parts) == 24 * 3

    def test_bf16_between_gate_up_and_down_in_both_forms(self):
        """bf16 operands: each product accumulates in float32 and hands
        on bf16, in the kernel as in `ragged_dot`; the two agree within
        bf16 steps of the result."""
        from hyperion_tpu.ops import moe

        p, x, picked, w = _expert_layer(d=128, f=128)
        p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        x = x.astype(jnp.bfloat16)
        want, _ = moe.grouped_experts(x, picked, w, p, held=(0, 8))
        with _Forced():
            got, _ = moe.grouped_experts(x, picked, w, p, held=(0, 8))
        assert got.dtype == want.dtype == jnp.bfloat16
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) < 0.02 * scale

    def test_off_a_tpu_the_program_is_ragged_dots_text(self):
        """The selector sends nothing to the kernel off a TPU: the
        traced expert step holds three `ragged_dot`s and no Pallas
        call; sent to the kernel, it holds no `ragged_dot`."""
        from hyperion_tpu.ops import moe

        p, x, picked, w = _expert_layer()
        text = str(jax.make_jaxpr(lambda *a: moe.grouped_experts(
            *a, held=(0, 8)))(x, picked, w, p))
        assert text.count("ragged_dot_general[") == 3
        assert "pallas_call" not in text
        with _Forced():
            text = str(jax.make_jaxpr(lambda *a: moe.grouped_experts(
                *a, held=(0, 8)))(x, picked, w, p))
        assert "ragged_dot_general[" not in text
        assert "_grouped_matmul" in text


# (rows, groups, k, n) of the cells' expert steps: SmallThinker's tick
# (48 slots x 6 picks over 64 experts of [2560, 768]), its 512-token
# chunk, its smallest prefill bucket; Trinity's tick (24 slots x 4
# picks, 32 held experts of [3072, 3072]) and chunk
CELL_SHAPES = {
    "smallthinker_tick": (288, 64, 2560, 768),
    "smallthinker_chunk": (3072, 64, 2560, 768),
    "smallthinker_bucket_8": (48, 64, 2560, 768),
    "trinity_tick": (96, 32, 3072, 3072),
    "trinity_chunk": (2048, 32, 3072, 3072),
}


@pytest.mark.parametrize("shape, backend, itemsize, want", [
    # off a TPU: never the kernel, whatever the shape
    *[pytest.param(s, b, 2, "ragged", id=f"{name}_on_{b}")
      for name, s in CELL_SHAPES.items() for b in ("cpu", "gpu")],
    # on a TPU: the shapes the probe measured the kernel to win at
    *[pytest.param(s, "tpu", 2, "kernel", id=f"{name}_on_tpu")
      for name, s in CELL_SHAPES.items()],
    # and nothing it did not measure: small matrices (a copy under a
    # few megabytes no longer hides a grid step), many rows a group (a
    # visit's product outgrows its copy), rows that fill no row tile
    pytest.param((288, 64, 512, 768), "tpu", 2, "ragged",
                 id="matrices_under_3_MiB"),
    pytest.param((288, 64, 2560, 768), "tpu", 1, "ragged",
                 id="the_same_in_one_byte_elements"),
    pytest.param((288, 64, 1024, 768), "tpu", 4, "kernel",
                 id="float32_matrices_of_3_MiB"),
    pytest.param((64 * 80, 64, 2560, 768), "tpu", 2, "ragged",
                 id="80_rows_a_group"),
    pytest.param((3072, 32, 2560, 768), "tpu", 2, "ragged",
                 id="half_the_groups_for_the_same_rows"),
    pytest.param((6, 64, 2560, 768), "tpu", 2, "ragged",
                 id="one_token_fills_no_row_tile"),
    pytest.param((40, 64, 2560, 768), "tpu", 2, "ragged",
                 id="rows_not_in_whole_tiles"),
])
def test_select_grouped_impl(shape, backend, itemsize, want):
    """The selector's table: a rule on the rows, the groups and the
    matrices' bytes, and the backend; no model's name in it."""
    from hyperion_tpu.ops.moe import select_grouped_impl

    from hyperion_tpu.ops import moe
    from hyperion_tpu.ops.pallas import grouped_matmul

    # the grain the rule asks of the rows is the kernel's smallest tile
    assert moe.GROUPED_KERNEL_ROW_GRAIN == grouped_matmul._ROW_TILES[-1] \
        == grouped_matmul._ROW_GRAIN
    assert select_grouped_impl(*shape, backend, itemsize) == want
    if itemsize == 2:
        assert select_grouped_impl(*shape, backend) == want
