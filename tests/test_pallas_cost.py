"""The `pl.CostEstimate` each Pallas call hands the compiler.

A trace's reader sets a kernel's seconds against these counts
(`obs/xprof.py`: the share of the roofline of a row that holds a
kernel), so a miscount shows up as a wrong roofline and nowhere else.
Each case reads the estimate off the traced program (the `pallas_call`
equation's own parameter, no run) on a small shape and holds it to the
count written out by hand: what the algorithm multiplies, and every
operand and result moved once."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401
from hyperion_tpu.ops.pallas import (
    fused_ce,
    fused_norm,
    grouped_matmul,
    paged_attention,
    window_attention,
)
from hyperion_tpu.ops.pallas.backend import LANES, SUBLANES, cost

flash = sys.modules["hyperion_tpu.ops.pallas.flash_attention"]

F32, BF16 = jnp.float32, jnp.bfloat16


def _estimates(fn, *args) -> list:
    """The `cost_estimate` of every `pallas_call` in `fn`'s program, in
    program order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["cost_estimate"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _nbytes(*shaped) -> int:
    return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
               for shape, dt in shaped)


def test_cost_counts_every_array_once_and_the_extra():
    a = jnp.zeros((3, 5), BF16)
    b = jax.ShapeDtypeStruct((7,), jnp.int32)
    c = cost(11, 13, a, b, extra_bytes=17)
    assert (c.flops, c.transcendentals) == (11, 13)
    assert c.bytes_accessed == 3 * 5 * 2 + 7 * 4 + 17


@pytest.mark.parametrize("Tq, Tkv, causal, want", [
    (8, 8, False, 64),
    (8, 8, True, 36),               # the triangle with its diagonal
    (4, 8, True, 10),               # 4 queries against the first 4 keys
    (8, 4, True, 10 + 4 * 4),       # 4 more queries that see all 4 keys
])
def test_scores_under_the_causal_mask(Tq, Tkv, causal, want):
    assert flash._scores(1, 1, Tq, Tkv, causal) == want
    assert flash._scores(2, 3, Tq, Tkv, causal) == 6 * want


B, T, H, D = 2, 256, 2, 64


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward(causal):
    q = jnp.zeros((B, T, H, D), BF16)
    (est,) = _estimates(
        lambda q: flash.flash_attention(q, q, q, causal=causal), q)
    scores = B * H * (T * (T + 1) // 2 if causal else T * T)
    # QK^T and PV: two products of 2 x D FLOPs a score; one exp a score
    assert est.flops == 4 * scores * D
    assert est.transcendentals == scores
    # q, k, v in and o out; no row statistics where no gradient is taken
    assert est.bytes_accessed == _nbytes(*[((B, H, T, D), BF16)] * 4)


def test_flash_backward_dq_and_dkv():
    q = jnp.zeros((B, T, H, D), BF16)
    mask = jnp.ones((B, T), jnp.int32)
    fwd, dq, dkv = _estimates(jax.grad(
        lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True, padding_mask=mask).astype(F32).sum(),
        argnums=(0, 1, 2)), q, q, q)
    scores = B * H * T * (T + 1) // 2
    tile = ((B, H, T, D), BF16)
    stats = ((B, H, T, LANES), F32)                 # lse, delta
    pad = ((B, SUBLANES, T), jnp.int32)             # the padding mask
    assert fwd.flops == 4 * scores * D
    # under a gradient the forward also writes lse, lane-wide
    assert fwd.bytes_accessed == _nbytes(*[tile] * 4, stats, pad)
    # the scores again, dP = dO V^T, dQ = dS K: three products
    assert (dq.flops, dq.transcendentals) == (6 * scores * D, scores)
    # the scores again, dV, dP, dK: four
    assert (dkv.flops, dkv.transcendentals) == (8 * scores * D, scores)
    # q, k, v, dO in, two statistics and the mask, one gradient out
    assert dq.bytes_accessed == _nbytes(*[tile] * 5, stats, stats, pad)
    assert dkv.bytes_accessed == _nbytes(*[tile] * 6, stats, stats, pad)


def test_fused_ce_forward_and_backward():
    N, V = 512, 4096
    logits = jnp.zeros((N, V), BF16)
    targets = jnp.zeros((N,), jnp.int32)
    fwd, bwd = _estimates(jax.grad(
        lambda lg: fused_ce.fused_softmax_xent(lg, targets).sum()), logits)
    assert (fwd.flops, fwd.transcendentals) == (4 * N * V, N * V + N)
    # the logits once, the targets lane-wide, loss and lse lane-wide
    assert fwd.bytes_accessed == _nbytes(
        ((N, V), BF16), ((N, LANES), jnp.int32), *[((N, LANES), F32)] * 2)
    assert (bwd.flops, bwd.transcendentals) == (3 * N * V, N * V)
    # logits in, gradient out, targets, lse and g lane-wide
    assert bwd.bytes_accessed == _nbytes(
        *[((N, V), BF16)] * 2, ((N, LANES), jnp.int32),
        *[((N, LANES), F32)] * 2)


@pytest.mark.parametrize("kind, per_element, rows_moved, vectors", [
    ("layernorm", 8, 2, 2),             # x in, y out; weight and bias
    ("layernorm_residual", 9, 3, 2),    # and the residual in
    ("rmsnorm", 4, 2, 1),
])
def test_fused_norm(kind, per_element, rows_moved, vectors):
    rows, d = 512, 256
    x = jnp.zeros((2, rows // 2, d), BF16)
    w = jnp.ones((d,), F32)
    fn = {
        "layernorm": lambda x: fused_norm.fused_layernorm(x, w, w),
        "layernorm_residual": lambda x: fused_norm.fused_layernorm(
            x, w, w, residual=x),
        "rmsnorm": lambda x: fused_norm.fused_rmsnorm(x, w),
    }[kind]
    (est,) = _estimates(fn, x)
    assert est.flops == per_element * rows * d
    assert est.transcendentals == rows          # one rsqrt a row
    assert est.bytes_accessed == _nbytes(
        *[((rows, d), BF16)] * rows_moved, *[((d,), F32)] * vectors)


@pytest.mark.parametrize("Tw", [1, 3])
def test_paged_attention_reads_chains_not_pools(Tw):
    Bs, Hq, Hkv, Dh, bs, MB, blocks = 4, 8, 2, 128, 16, 6, 64
    q = jnp.zeros((Bs, Tw, Hq, Dh), BF16)
    pool = jnp.zeros((blocks, Hkv, bs, Dh), BF16)
    tables = jnp.zeros((Bs, MB), jnp.int32)
    base = jnp.zeros((Bs,), jnp.int32)
    (est,) = _estimates(
        lambda q, k, v: paged_attention.paged_attention(
            q, k, v, tables, base), q, pool, pool)
    rows = Tw * Hq // Hkv                       # a KV head's query group
    rows_p = -(-rows // SUBLANES) * SUBLANES
    # the static worst case, every table entry live: the walk stops at
    # each slot's frontier, which is runtime data no estimate can see
    # (here `base` is 0 and the call would move one block a slot)
    chain = Bs * MB * Hkv * bs                  # key rows the tables name
    assert est.flops == 4 * chain * rows * Dh
    assert est.transcendentals == chain * rows
    # every table entry's block of K and of V once (`extra_bytes`), the
    # padded query group in and out, tables and bases: NOT the pools
    assert est.bytes_accessed == (
        2 * chain * Dh * 2
        + _nbytes(*[((Bs, Hkv, rows_p, Dh), BF16)] * 2,
                  ((Bs, MB), jnp.int32), ((Bs,), jnp.int32)))
    assert est.bytes_accessed < _nbytes((pool.shape, BF16))


@pytest.mark.parametrize("Tw, window, walk", [
    (1, 64, 5),       # 64 positions anywhere over 16-token blocks: 5
    (3, 64, 6),       # three queries' windows: 66 positions, 6 blocks
    (1, 4096, 12),    # a window wider than the table: the table
])
def test_windowed_paged_attention_counts_the_window_not_the_table(
        Tw, window, walk):
    Bs, Hq, Hkv, Dh, bs, MB, blocks = 4, 8, 2, 128, 16, 12, 64
    q = jnp.zeros((Bs, Tw, Hq, Dh), BF16)
    pool = jnp.zeros((blocks, Hkv, bs, Dh), BF16)
    tables = jnp.zeros((Bs, MB), jnp.int32)
    base = jnp.zeros((Bs,), jnp.int32)
    (est,) = _estimates(
        lambda q, k, v: paged_attention.paged_attention(
            q, k, v, tables, base, window=window), q, pool, pool)
    assert walk == min(MB, paged_attention.window_view_blocks(
        window, Tw, bs))
    rows = Tw * Hq // Hkv
    rows_p = -(-rows // SUBLANES) * SUBLANES
    # the static worst case of a windowed call: the blocks a window can
    # span a slot, whatever the table's width
    chain = Bs * walk * Hkv * bs
    assert est.flops == 4 * chain * rows * Dh
    assert est.transcendentals == chain * rows
    assert est.bytes_accessed == (
        2 * chain * Dh * 2
        + _nbytes(*[((Bs, Hkv, rows_p, Dh), BF16)] * 2,
                  ((Bs, MB), jnp.int32), ((Bs,), jnp.int32)))


@pytest.mark.parametrize("window, keys_a_query", [
    (0, 512 - 128 // 2),   # under the diagonal of a window at the view's end
    (192, 192),            # a windowed layer: its window
    (4096, 512),           # a window wider than the view: the view
])
def test_window_attention_counts_the_mask_not_the_square(
        window, keys_a_query):
    B, T, Hq, Hkv, Dh, bs, NBv = 2, 128, 8, 2, 128, 16, 32   # 512 keys
    q = jnp.zeros((B, T, Hq, Dh), BF16)
    view = jnp.zeros((B, NBv, Hkv, bs, Dh), BF16)
    zero = jnp.zeros((B,), jnp.int32)
    (est,) = _estimates(
        lambda q, k, v: window_attention.window_attention(
            q, k, v, zero, zero, window=window), q, view, view)
    # the static worst case of the mask: what a call multiplies follows
    # `base`, which no estimate can see (here 0: a quarter of it)
    pairs = B * Hq * T * keys_a_query
    assert est.flops == 4 * pairs * Dh
    assert est.transcendentals == pairs
    # the regrouped query in, the output out, the view's keys and
    # values once each, the two scalar rows: a tile read again for the
    # next query tile is the tiling's, not the algorithm's
    assert est.bytes_accessed == _nbytes(
        *[((B, Hkv, T * Hq // Hkv, Dh), BF16)] * 2,
        *[(view.shape, BF16)] * 2, *[((B,), jnp.int32)] * 2)


@pytest.mark.parametrize("M, G, K, N, tiling", [
    pytest.param(64, 8, 256, 128, (16, 128), id="gate_up_orientation"),
    pytest.param(64, 8, 128, 256, (32, 128), id="down_cut_along_columns"),
    pytest.param(50, 4, 128, 128, (16, 128), id="rows_padded_to_a_tile"),
])
def test_grouped_matmul_counts_rows_once_and_every_matrix_once(
        M, G, K, N, tiling):
    lhs = jnp.zeros((M, K), BF16)
    rhs = jnp.zeros((G, K, N), BF16)
    sizes = jnp.zeros((G,), jnp.int32)
    (est,) = _estimates(
        lambda a, b: grouped_matmul.grouped_matmul(
            a, b, sizes, tiling=tiling), lhs, rhs)
    Mp = -(-M // tiling[0]) * tiling[0]
    visits = Mp // tiling[0] + G - 1
    # the rows' own products, not the row tiles a visit multiplies
    assert est.flops == 2 * Mp * K * N
    assert est.transcendentals == 0
    # the static worst case, every group touched: rows in and result
    # out once, every group's matrix once (what a call reads follows
    # `sizes`, runtime data), and the walk's lists; no tiling's
    # re-reads
    assert est.bytes_accessed == _nbytes(
        ((Mp, K), BF16), ((G, K, N), BF16), ((Mp, N), BF16),
        ((G + 1,), jnp.int32), *[((visits,), jnp.int32)] * 2,
        ((1,), jnp.int32))
