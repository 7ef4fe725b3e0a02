"""Compile the Pallas kernels for a described TPU v5e, device-free.

Interpret-mode tests (`test_pallas_kernels.py`) prove numerics and never
meet the chip's compiler. Lowering through `jax.export` meets Mosaic's
block-shape check and stops there: fused CE passed it and was then
refused by the real compile (XLA and Mosaic disagreed on the layout of
its 1-D per-row operands), and the paged-attention pool block was
refused at every Llama width. So these tests run the whole compile,
`jit(...).lower(...).compile()`, against a topology that is described
and not attached, at the widths the server and the trainer use, and
assert that the compiled program holds a `tpu_custom_call`.

This is the only file that describes a topology, and it does so inside
a module-scoped fixture: only one process may load the TPU's library,
so the call must not run while any module is imported, and must run in
the test's own process. A compile that passes here is not a chip run;
`chip_smoke.py` is.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401
import hyperion_tpu.ops.pallas.fused_ce as ce_mod
import hyperion_tpu.ops.pallas.fused_norm as norm_mod
import hyperion_tpu.ops.pallas.grouped_matmul as grouped_mod
import hyperion_tpu.ops.pallas.paged_attention as paged_mod
import hyperion_tpu.ops.pallas.state_update as state_mod
import hyperion_tpu.ops.pallas.window_attention as window_mod

# the package re-exports the flash_attention function under the module's
# own name, so `import ... as` would bind the function
flash_mod = sys.modules["hyperion_tpu.ops.pallas.flash_attention"]

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache and cannot be read back without one: the next
    # run would warn and compile again, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The session's backend is the CPU, where `_interpret()` picks the
    interpreter; steer the seven kernels to the compiled path here, in
    the test, rather than through an option of the program."""
    for mod in (flash_mod, ce_mod, norm_mod, paged_mod, grouped_mod,
                window_mod, state_mod):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, one_chip, *avals):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's own count of its work reaches the compiler (and, on
    # the chip, a trace's `flops` / `bytes_accessed`): without it a
    # program's cost is blind where the kernel is
    assert 'cost_estimate":{"flops":"' in text
    return text


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# Llama-2-7B is MHA (32 KV heads); the 8-KV-head GQA geometry is the
# 70B/Llama-3 ratio at the same head_dim. Pool: 8 slots x 2048 tokens in
# 16-token blocks (+ the null block), as chip_smoke.py serves.
PAGED_HEADS = {"mha": (32, 32), "gqa": (32, 8)}
PAGED_GEOMETRY = {"decode": (8, 1), "verify": (8, 5), "chunk": (1, 256)}


class TestPagedAttentionCompiles:
    @pytest.mark.parametrize("geometry", sorted(PAGED_GEOMETRY))
    @pytest.mark.parametrize("heads", sorted(PAGED_HEADS))
    def test_llama_widths(self, mosaic, one_chip, heads, geometry):
        H, Hkv = PAGED_HEADS[heads]
        B, T = PAGED_GEOMETRY[geometry]
        D, bs, MB = 128, 16, 128
        pool = S((8 * MB + 1, Hkv, bs, D), BF16)
        _compile(
            paged_mod.paged_attention, one_chip,
            S((B, T, H, D), BF16), pool, pool,
            S((B, MB), jnp.int32), S((B,), jnp.int32),
        )


    @pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify"])
    def test_benchmark_cell_geometry(self, mosaic, one_chip, window):
        """`mistral7b.chat-saturated`'s tick: 48 slots, 32 heads on 8 KV
        heads of 128, 16-token blocks, 128 table columns, a pool of
        6144 blocks; and a verify window of 4 over the same slots. The
        plan takes every KV head in one step."""
        S_, H, Hkv, D, bs, MB, NB = 48, 32, 8, 128, 16, 128, 6144
        rows_p = -(-window * H // Hkv // 8) * 8
        assert paged_mod._plan(rows_p, Hkv, bs, D, 2, MB) == (Hkv, 64)
        pool = S((NB, Hkv, bs, D), BF16)
        text = _compile(
            paged_mod.paged_attention, one_chip,
            S((S_, window, H, D), BF16), pool, pool,
            S((S_, MB), jnp.int32), S((S_,), jnp.int32),
        )
        # the pools are read where they lie: no operand of the kernel
        # is a gathered or re-laid-out copy of them
        assert "gather" not in text

    @pytest.mark.parametrize("kind", ["full", "window"])
    def test_trinity_tick_geometry(self, mosaic, one_chip, kind):
        """`trinity.longmix-saturated`'s tick, the two calls its five
        layers make: 24 slots of one token, 48 heads on 8 KV heads of
        128 (6 query rows a KV head), 16-token blocks, tables of 768
        columns; the full kind's pool of 18433 blocks walked from block
        0, the windowed kind's of 6961 from the block of `base - 4095`.
        One plan for both: every KV head in a step, groups of 64."""
        S_, H, Hkv, D, bs, MB = 24, 48, 8, 128, 16, 768
        window, NB = {"full": (0, 18433), "window": (4096, 6961)}[kind]
        walk = paged_mod.window_view_blocks(window, 1, bs) if window else MB
        assert walk == {"full": 768, "window": 257}[kind]
        assert paged_mod._plan(8, Hkv, bs, D, 2, walk) == (Hkv, 64)
        pool = S((NB, Hkv, bs, D), BF16)
        text = _compile(
            lambda *a: paged_mod.paged_attention(*a, window=window),
            one_chip,
            S((S_, 1, H, D), BF16), pool, pool,
            S((S_, MB), jnp.int32), S((S_,), jnp.int32),
        )
        assert "gather" not in text


    def test_ouro_tick_geometry(self, mosaic, one_chip):
        """`ouro.shortchat-saturated`'s tick, the call each of its three
        scans makes: 8 slots of one token, 16 heads on 16 KV heads of
        128 (ONE query row a KV head: a block's copy is 64 KiB), tables
        of 48 columns, a pool that holds 16 layers x 4 steps segments
        of 385 blocks. Every KV head in a step, a slot's whole chain in
        one group."""
        S_, H, D, bs, MB, NB = 8, 16, 128, 16, 48, 64 * 385
        assert paged_mod._plan(8, H, bs, D, 2, MB) == (H, 32)
        pool = S((NB, H, bs, D), BF16)
        assert NB * H * bs * D * 2 < 2 ** 31        # a pool's bytes
        text = _compile(
            paged_mod.paged_attention, one_chip,
            S((S_, 1, H, D), BF16), pool, pool,
            S((S_, MB), jnp.int32), S((S_,), jnp.int32),
        )
        assert "gather" not in text


# The prompt windows of the benchmark's cells, one layer's read: query
# positions, KV heads, query heads a KV head, table columns, window.
# Head size 128 and 16-position blocks throughout.
WINDOW_CELLS = {
    "mistral_bucket_2048": (2048, 8, 4, 128, 0),
    "trinity_chunk_windowed": (512, 8, 6, 768, 4096),
    "trinity_chunk_full": (512, 8, 6, 768, 0),
    "smallthinker_chunk_windowed": (512, 4, 7, 512, 4096),
    "smallthinker_chunk_full": (512, 4, 7, 512, 0),
}


class TestWindowAttentionCompiles:
    @pytest.mark.parametrize("cell", sorted(WINDOW_CELLS))
    def test_benchmark_cell_geometry(self, mosaic, one_chip, cell):
        """A prompt window's tiled read (`paged_read("tiled")`: the
        gather of the chain in whole blocks, then the kernel over it)
        at each cell's widths: the plan's step fits the VMEM budget it
        states, the kernel is there, the view goes from the gather to
        the kernel as it lies, and no float32 array wider than the
        query itself, let alone `[rows, view]` of scores, is left in
        the program."""
        import re

        from hyperion_tpu.models import llama

        T, Hkv, rep, MB, window = WINDOW_CELLS[cell]
        D, bs, NB = 128, 16, 6145
        nb = min(MB, paged_mod.window_view_blocks(window, T, bs)) \
            if window else MB
        assert nb == (289 if window else MB)
        tq, tk = window_mod._plan(T, rep, D, bs, nb, BF16, BF16)
        assert (tq, tk) == (256, 1024)
        assert window_mod.plan_vmem_bytes(tq, tk, rep, D, 2, 2) \
            <= window_mod._VMEM_BUDGET < window_mod._VMEM_LIMIT
        pool = S((NB, Hkv, bs, D), BF16)
        text = _compile(
            lambda q, ck, cv, bt, base: llama.paged_read(
                "tiled", q, ck, cv, bt, base, window),
            one_chip,
            S((1, T, Hkv * rep, D), BF16), pool, pool,
            S((1, MB), jnp.int32), S((1,), jnp.int32),
        )
        # the view in whole key tiles, in the cache's dtype
        blocks = -(-nb * bs // tk) * tk // bs
        assert f"bf16[1,{blocks},{Hkv},{bs},{D}]" in text
        f32 = [int(np.prod([int(d) for d in dims.split(",")]))
               for dims in re.findall(r"f32\[([0-9,]+)\]", text)]
        # the query scaled in float32 inside its fusion, nothing wider
        assert max(f32, default=0) <= T * Hkv * rep * D < T * Hkv * rep * tk


class TestOuroProgramsStayInPlace:
    """The looped model's tick and prefill at the published widths, one
    scan of 16 layers (the cell runs three such scans, one after the
    other: a program's temporaries are those of one): the steps and the
    layers are loops, the pools go through them aliased input to
    output, and nothing of a pool's size is among the temporaries."""

    def _compile(self, one_chip, program):
        from hyperion_tpu.models.llama import init_paged_cache
        from hyperion_tpu.models.ouro import Ouro, OuroConfig
        from hyperion_tpu.serve import engine as E

        slots, L, bs = 8, 768, 16
        mb = L // bs
        cfg = OuroConfig(
            n_layers=16, pool_layers=16, max_len=L,
            paged_attn_impl="pallas" if program == "tick" else "gather")
        model = Ouro(cfg)

        def on_chip(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        params = jax.tree_util.tree_map_with_path(
            lambda path, a: jax.ShapeDtypeStruct(
                a.shape, jnp.float32 if path[-1].key == "weight" else BF16,
                sharding=one_chip),
            jax.eval_shape(lambda: model.init_params(jax.random.key(0))))
        cache = on_chip(jax.eval_shape(
            lambda: init_paged_cache(cfg, slots * mb + 1, bs)))
        st = on_chip(jax.eval_shape(lambda: {
            "lengths": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "last_token": jnp.zeros((slots,), jnp.int32),
            "generated": jnp.zeros((slots,), jnp.int32),
            "budget": jnp.ones((slots,), jnp.int32),
            "temperature": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "keys": jax.random.split(jax.random.key(0), slots)}))

        def of(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        i32, f32 = jnp.int32, jnp.float32
        if program == "tick":
            lowered = jax.jit(
                E._tick_impl, static_argnums=(0, 1, 2),
                donate_argnums=(4, 5),
            ).lower(model, None, 0, {"params": params}, cache, st,
                    {"full": of(i32, slots, mb)}, of(bool, slots))
        else:
            lowered = jax.jit(
                E._prefill_impl, static_argnums=(0, 1),
                donate_argnums=(3, 4),
            ).lower(model, None, {"params": params}, cache, st,
                    of(i32, 1, 512), {"full": of(i32, mb)}, of(i32),
                    of(i32), of(i32), of(f32), of(i32), of(f32), of(i32),
                    on_chip(jax.eval_shape(lambda: jax.random.key(0))))
        pools = [a.size * a.dtype.itemsize for a in jax.tree.leaves(cache)]
        return lowered.compile(), pools

    @pytest.mark.parametrize("program", ["tick", "prefill"])
    def test_no_pool_sized_temporary(self, mosaic, one_chip, program):
        compiled, pools = self._compile(one_chip, program)
        assert len(pools) == 2 and pools[0] == 64 * 385 * 16 * 16 * 128 * 2
        ma = compiled.memory_analysis()
        assert ma.alias_size_in_bytes >= sum(pools)
        # 5-6 MiB at 48 layers (PERF.md section 6, PR 34): not a pool
        # (1.5 GiB), not a scan's q, k, v kernels transposed (384 MiB)
        assert ma.temp_size_in_bytes < 64 * 2 ** 20
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == (program == "tick")


class TestGroupedMatmulCompiles:
    """The grouped-matmul kernel at the shapes `select_grouped_impl`
    sends it on a TPU, and the tick that holds it."""

    # (rows, groups, d, f) of the cells' expert steps
    SMALLTHINKER_TICK = (48 * 6, 64, 2560, 768)
    SMALLTHINKER_CHUNK = (512 * 6, 64, 2560, 768)
    TRINITY_TICK = (24 * 4, 32, 3072, 3072)
    TRINITY_CHUNK = (512 * 4, 32, 3072, 3072)

    def test_the_selector_sends_the_ticks_shape_of_smallthinker(self):
        from hyperion_tpu.ops.moe import select_grouped_impl

        assert select_grouped_impl(*self.SMALLTHINKER_TICK, "tpu") == "kernel"

    @pytest.mark.parametrize("shape", [
        "SMALLTHINKER_TICK", "SMALLTHINKER_CHUNK", "TRINITY_TICK",
        "TRINITY_CHUNK"])
    def test_a_layers_three_products(self, mosaic, one_chip, shape):
        """One layer's gate, up and down at the cell's widths with the
        plan's own tiling, the walk listed once: compiles whether or
        not the selector sends the shape (a shape it leaves on
        `ragged_dot` today stays a shape the kernel can take)."""
        rows, groups, d, f = getattr(self, shape)

        def layer(xs, gate, up, down, sizes):
            v = grouped_mod.group_visits(
                sizes, rows, grouped_mod.row_tile(rows))
            g = grouped_mod.grouped_matmul(xs, gate, sizes, visits=v)
            u = grouped_mod.grouped_matmul(xs, up, sizes, visits=v)
            return grouped_mod.grouped_matmul(
                jax.nn.relu(g) * u, down, sizes, visits=v)

        text = _compile(
            layer, one_chip, S((rows, d), BF16), S((groups, d, f), BF16),
            S((groups, d, f), BF16), S((groups, f, d), BF16),
            S((groups,), jnp.int32))
        assert text.count("tpu_custom_call") >= 3

    def test_smallthinkers_tick_holds_it_and_copies_no_expert_stack(
            self, mosaic, one_chip, monkeypatch):
        """The decode tick of `smallthinker.chatmix-saturated` (48
        slots, the published widths, two of its layers: one full, one
        windowed) with both selectors answering as on a TPU: the three
        products of each layer are the kernel, the pools and the expert
        stacks are read where they lie, and no temporary is the size
        of a stack (252 MB a matrix)."""
        from hyperion_tpu.models import llama, smallthinker
        from hyperion_tpu.models.llama import init_paged_cache
        from hyperion_tpu.ops import moe
        from hyperion_tpu.serve import engine as E

        paged, grouped = llama.select_paged_attn_impl, \
            moe.select_grouped_impl
        for mod in (llama, smallthinker):
            monkeypatch.setattr(
                mod, "select_paged_attn_impl",
                lambda window, rep, backend: paged(window, rep, "tpu"))
        monkeypatch.setattr(
            moe, "select_grouped_impl",
            lambda rows, groups, k, n, backend, itemsize=2: grouped(
                rows, groups, k, n, "tpu", itemsize))
        slots, L, bs = 48, 1024, 16
        mb = L // bs
        # the vocabulary cut to 8192: the head is not this test's
        cfg = smallthinker.SmallthinkerConfig(
            sliding_window_layout=(0, 1), rope_layout=(0, 1), max_len=L,
            vocab_size=8192)
        model = smallthinker.Smallthinker(cfg)

        def on_chip(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        params = on_chip(jax.eval_shape(
            lambda: model.init_params(jax.random.key(0))))
        stack = 64 * 2560 * 768 * 2
        assert params["layer_0"]["moe"]["experts_gate"].dtype == BF16
        cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
            cfg, {"full": slots * mb + 1, "window": slots * mb + 1}, bs)))
        st = on_chip(jax.eval_shape(lambda: {
            "lengths": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "last_token": jnp.zeros((slots,), jnp.int32),
            "generated": jnp.zeros((slots,), jnp.int32),
            "budget": jnp.ones((slots,), jnp.int32),
            "temperature": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "keys": jax.random.split(jax.random.key(0), slots)}))
        table = jax.ShapeDtypeStruct((slots, mb), jnp.int32,
                                     sharding=one_chip)
        compiled = jax.jit(
            E._tick_impl, static_argnums=(0, 1, 2), donate_argnums=(4, 5),
        ).lower(model, None, 0, {"params": params}, cache, st,
                {"full": table, "window": table},
                jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip),
                ).compile()
        text = compiled.as_text()
        # a layer: the read kernel and three grouped products
        assert text.count("tpu_custom_call") == 2 * 4
        assert "ragged-dot" not in text
        ma = compiled.memory_analysis()
        # 8 MB here; a copied stack would be 252
        assert ma.temp_size_in_bytes < stack // 8


class TestStateUpdateCompiles:
    """The state-update kernel at the decode tick's shape of
    `solar2.chat-saturated`: 128 slots' rows of 64 heads of [128, 128]
    float32, S aliased through a donated argument, the visits counted
    at run time."""

    def test_the_selector_sends_the_ticks_shape(self):
        from hyperion_tpu.models.solar_open2 import (
            select_state_update_impl,
        )

        assert select_state_update_impl(1, True, 128, 128, "tpu") == "kernel"

    def test_the_cells_tick_shape_in_place(self, mosaic, one_chip):
        B, H, K = 128, 64, 128
        A = jnp.float32
        args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
                for shape, dt in (((B, H, K), A), ((B, H, K), A),
                                  ((B, H, K), A), ((B, H, K), A),
                                  ((B, H), A), ((B, H, K, K), A),
                                  ((B,), jnp.bool_))]
        compiled = jax.jit(state_mod.delta_rule_step_rows,
                           donate_argnums=(5,)).lower(*args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        assert 'cost_estimate":{"flops":"' in text
        ma = compiled.memory_analysis()
        # S (537 MB) goes out where it came in: no copy of it
        assert ma.alias_size_in_bytes == B * H * K * K * 4
        assert ma.temp_size_in_bytes < 1 << 20


class TestFlashAttentionCompiles:
    @pytest.mark.parametrize("shape", [
        (8, 1024, 12, 64),     # the reference LM's heads at seq 1024
        (2, 4096, 32, 128),    # Llama-2-7B heads at full context
    ], ids=["d64_t1024", "d128_t4096"])
    def test_fwd_bwd(self, mosaic, one_chip, shape):
        def loss(q, k, v):
            out = flash_mod.flash_attention(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum()

        a = S(shape, BF16)
        _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, a, a, a)

    def test_fwd_unaligned_seq(self, mosaic, one_chip):
        a = S((2, 200, 32, 128), BF16)
        _compile(
            lambda q, k, v: flash_mod.flash_attention(q, k, v, causal=True),
            one_chip, a, a, a)


class TestFusedCECompiles:
    @pytest.mark.parametrize("vocab,dtype", [
        (50257, jnp.float32), (50257, BF16), (32000, BF16),
    ], ids=["gpt2_f32", "gpt2_bf16", "llama_bf16"])
    def test_fwd_bwd(self, mosaic, one_chip, vocab, dtype):
        N = 32 * 127  # the trainer's [batch, seq-1] rows, not a tile multiple

        def loss(logits, targets):
            return ce_mod.fused_softmax_xent(logits, targets).mean()

        _compile(jax.grad(loss), one_chip,
                 S((N, vocab), dtype), S((N,), jnp.int32))


class TestFusedNormCompiles:
    def test_rmsnorm_llama_width(self, mosaic, one_chip):
        def loss(x, w):
            y = norm_mod.fused_rmsnorm(x, w)
            return (y.astype(jnp.float32) ** 2).sum()

        _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                 S((2, 1024, 4096), BF16), S((4096,), jnp.float32))

    def test_rmsnorm_decode_shape(self, mosaic, one_chip):
        _compile(norm_mod.fused_rmsnorm, one_chip,
                 S((8, 1, 4096), BF16), S((4096,), jnp.float32))

    def test_layernorm_lm_width(self, mosaic, one_chip):
        def loss(x, r, w, b):
            y = norm_mod.fused_layernorm(x, w, b, residual=r)
            return (y.astype(jnp.float32) ** 2).sum()

        x = S((32, 128, 768), BF16)
        v = S((768,), jnp.float32)
        _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip, x, x, v, v)


def _computations(hlo_text):
    """{name: body} of a compiled module's computations."""
    out, name, body = {}, None, []
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split()
            name = head[1] if head[0] == "ENTRY" else head[0]
            name, body = name.lstrip("%"), []
        elif line.startswith("}") and name is not None:
            out[name] = "\n".join(body)
            name = None
        elif name is not None:
            body.append(line)
    return out


class TestSamplingTiersCompile:
    """Not a kernel: the serving tick's sampling at the benchmark's
    shape. Lowering holds a `case`; whether the chip's compiler keeps a
    conditional, with the sorts inside the branch that needs them, is
    what only this compile shows (a select would run all three tiers on
    every tick)."""

    def test_conditional_survives_with_the_sorts_inside(self, one_chip):
        from hyperion_tpu.infer.generate import sample_token_slots

        rows, vocab = 48, 32000   # slots x Mistral's vocabulary

        def per_row(dtype, *trailing):
            return jax.ShapeDtypeStruct((rows, *trailing), dtype,
                                        sharding=one_chip)

        text = jax.jit(sample_token_slots).lower(
            per_row(jnp.float32, vocab),
            per_row(jax.eval_shape(jax.random.key, 0).dtype),
            per_row(jnp.float32), per_row(jnp.int32),
            per_row(jnp.float32), per_row(jnp.bool_),
        ).compile().as_text()
        comps = _computations(text)
        entry = next(b for n, b in comps.items() if " conditional(" in b)
        cond = next(ln for ln in entry.splitlines()
                    if " conditional(" in ln)
        branches = cond.split("branch_computations={")[1].split("}")[0]
        branches = [b.strip().lstrip("%") for b in branches.split(",")]
        assert len(branches) == 3

        def reachable(name, seen):
            if name in seen or name not in comps:
                return seen
            seen.add(name)
            for other in comps:
                if other != name and "%" + other in comps[name]:
                    reachable(other, seen)
            return seen

        def sorts(name):
            return sum(comps[c].count(" sort(")
                       for c in reachable(name, set()))

        assert sorts(branches[0]) == 0 and sorts(branches[1]) == 0
        assert sorts(branches[2]) >= 2
        # every sort of the program sits under the third branch
        assert sum(b.count(" sort(") for b in comps.values()) == \
            sorts(branches[2])


def _parent_paged_kv_write(cache, k, v, block_tables, base):
    """`models/llama.py` `paged_kv_write` as it stood before PR 32: the
    row scatter alone, whatever the window."""
    from hyperion_tpu.models import llama

    with jax.named_scope("kv_write"):
        return llama._kv_write_rows(
            cache["k"], cache["v"], k, v, block_tables, base)


class TestPrefillKvWriteCompiles:
    """Not a kernel: a prompt's `kv_write` inside the Mistral cell's
    `[1, 2048]` prefill: two layers of the cell's sixteen at its widths
    and its pool of 6144 blocks, over a vocabulary of 512 (the layers
    repeat; the sampler's sorts over 32000 ids are most of the whole
    program's 40 s compile and none of this test's business; PERF.md
    has the whole programs). What only the chip's compiler shows: that the
    run-time choice between the two grains stays a conditional with the
    row scatter in one branch and the block scatter in the other, that
    the pools stay aliased through it, and what it does to the
    program's temporaries."""

    LAYERS = 2

    def _prefill(self, one_chip, twin=False):
        from hyperion_tpu.models.llama import (
            Llama,
            LlamaConfig,
            init_paged_cache,
        )
        from hyperion_tpu.serve import engine as E

        class Twin(Llama):
            """The same model under another identity: a jit's traces
            are keyed by the model, so the twin is traced afresh."""

        cfg = LlamaConfig(
            vocab_size=512, d_model=4096, n_layers=self.LAYERS,
            n_heads=32, n_kv_heads=8, ff_dim=14336, max_len=2048,
            rope_theta=1e6, remat=False, dtype="bfloat16")
        model = (Twin if twin else Llama)(cfg)
        slots, bucket, mb = 48, 2048, 128

        def on_chip(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        params = on_chip(jax.eval_shape(
            lambda: model.init_params(jax.random.key(0))))
        cache = on_chip(jax.eval_shape(
            lambda: init_paged_cache(cfg, 6144, 16)))
        st = on_chip(jax.eval_shape(lambda: {
            "lengths": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "last_token": jnp.zeros((slots,), jnp.int32),
            "generated": jnp.zeros((slots,), jnp.int32),
            "budget": jnp.ones((slots,), jnp.int32),
            "temperature": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "keys": jax.random.split(jax.random.key(0), slots)}))

        def of(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        i32, f32 = jnp.int32, jnp.float32
        compiled = jax.jit(
            E._prefill_impl, static_argnums=(0, 1), donate_argnums=(3, 4),
        ).lower(
            model, None, {"params": params}, cache, st,
            of(i32, 1, bucket), {"full": of(i32, mb)}, of(i32), of(i32),
            of(i32), of(f32), of(i32), of(f32), of(i32),
            on_chip(jax.eval_shape(lambda: jax.random.key(0))),
        ).compile()
        pools = sum(a.size * a.dtype.itemsize
                    for a in jax.tree.leaves(cache))
        return compiled, pools

    def test_conditional_survives_and_the_pools_stay_in_place(
            self, one_chip, monkeypatch):
        from hyperion_tpu.models import llama

        compiled, pools = self._prefill(one_chip)
        comps = _computations(compiled.as_text())

        def reachable(name, seen):
            if name in seen or name not in comps:
                return seen
            seen.add(name)
            for other in comps:
                if other != name and "%" + other in comps[name]:
                    reachable(other, seen)
            return seen

        def scatters(name, operand):
            # the row scatter writes the pool seen as rows of D, the
            # block scatter the pool as it lies
            return sum(
                operand in ln for c in reachable(name, set())
                for ln in comps[c].splitlines() if " scatter(" in ln)

        rows, blocks = "bf16[786432,128]", "bf16[6144,8,16,128]"
        conds = [ln for b in comps.values() for ln in b.splitlines()
                 if " conditional(" in ln and blocks in ln]
        assert len(conds) == self.LAYERS
        for cond in conds:
            if "branch_computations={" in cond:
                names = cond.split("branch_computations={")[1].split("}")[0]
                names = [n.strip().lstrip("%") for n in names.split(",")]
            else:
                names = [cond.split(key + "=")[1].split(",")[0].split(")")[0]
                         .strip().lstrip("%")
                         for key in ("false_computation", "true_computation")]
            assert len(names) == 2
            # one branch row by row, the other block by block: two
            # pools each, and neither grain in the other's branch
            assert [scatters(n, rows) for n in names] == [2, 0]
            assert [scatters(n, blocks) for n in names] == [0, 2]
        # every pool scatter of the program sits under a conditional
        assert sum(ln.count(rows) > 0 or ln.count(blocks) > 0
                   for b in comps.values() for ln in b.splitlines()
                   if " scatter(" in ln) == 4 * self.LAYERS
        # in place: every pool is aliased input to output, and nothing
        # of a pool's size (402 MB here) is among the temporaries
        ma = compiled.memory_analysis()
        assert ma.alias_size_in_bytes >= pools
        one_pool = pools // (2 * self.LAYERS)
        # against the program the parent lowered: the row scatter alone
        monkeypatch.setattr(llama, "paged_kv_write", _parent_paged_kv_write)
        parent, _ = self._prefill(one_chip, twin=True)
        assert " conditional(" not in "".join(
            ln for b in _computations(parent.as_text()).values()
            for ln in b.splitlines() if blocks in ln)
        grown = ma.temp_size_in_bytes \
            - parent.memory_analysis().temp_size_in_bytes
        # the new keys' and values' block forms and the conditional's
        # operands (4 MiB each at this bucket) may lie in HBM where the
        # parent's lay in VMEM: 16 MiB at sixteen layers (PERF.md
        # section 6, PR 32), never a pool
        assert grown <= 4 * (2048 * 8 * 128 * 2) < one_pool // 8
