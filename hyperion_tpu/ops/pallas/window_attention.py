"""Prompt-window attention: a tiled online softmax over a gathered chain.

Role in the stack (ROADMAP S3.2): a prompt-length window (`[1, bucket]`
prefills, `[1, 512]` chunks) used to go from the gather to
`models/llama.py` `_grouped_cache_attention`, which wrote a
`[Hkv, rep, T, view]` float32 score array to HBM, read it back for the
mask and the softmax, wrote the weights and read them again for the
second product: 1.2 GB a layer for a 512-position chunk against a view
of 12288 keys, and the view is the table's whole width, not what the
prompt holds. Here the same masked product runs a tile at a time with
the statistics in VMEM, and key tiles no query of the window can see
are neither multiplied nor read.

Forward only and serving only. `flash_attention.py` is the training
kernel (equal lengths, a padding mask, a backward) and
`paged_attention.py` the decode tick's (many slots, a few rows each,
the pools read in place); the three share `backend.py` and nothing
else.

The walk:

  * The caller (`models.llama.paged_tiled_read`) gathers each row's
    chain as `pool[table]`, `[B, NBv, Hkv, bs, D]`, and hands it over
    as it lies: a key tile is `tk // bs` whole blocks of one KV head,
    `[tk // bs, bs, D]` in VMEM, which is `[tk, D]` without a copy
    because a block is whole tiles of the cache's dtype. Nothing
    transposes the view and nothing casts it.
  * Grid `(B, Hkv, query tiles, key tiles)`, the key axis innermost and
    "arbitrary": `m`, `l` (lane-replicated) and the accumulator live in
    VMEM scratch across a query tile's key sweep. The `rep` query heads
    of a KV head are folded into the query tile's rows (row `r` is
    token `r // rep`), as `_grouped_cache_attention` and the tick's
    kernel fold them, so K and V are never repeated.
  * `base[b]` and `view0[b]`, the position of the view's first key,
    ride in as scalar prefetch. From them a step knows the key tiles
    its queries can see, `_tile_span`: none past the tile's last query,
    none wholly before the first query's window. A step outside the
    span does nothing (`pl.when`), and its block index is clamped into
    the span, so the pipeline sees the block it already holds and
    starts no copy: a 512-position prompt in a view of 2048 reads one
    tile of it.
  * A tile every query of the step sees whole (below the diagonal,
    inside every window) skips the mask's arithmetic; the tiles on the
    diagonal and on a window's edge build it from two iotas.
  * `_plan` chooses the tiles from the call's static shape under the
    VMEM budget written beside it.

Masking contract, the gather path's to the letter: key `p` is seen by
the query at `q = base[b] + t` iff `p <= q` and, in a windowed layer,
`p > q - window`. Bucket padding past the frontier, blocks the engine
has let go (table entry 0: they lie before the window), the padding of
the view up to whole tiles and an inactive lane get weight exactly 0
(finite `NEG_INF`: `-inf` would make NaN in the rescale). Every query
tile runs at least one key tile, so `l > 0`: a row that sees nothing
there (a padding query beyond the view) averages that tile, finite
and ignored by the caller, as the gather path's such rows are.

Numerics: the same work as the gather path on a TPU. There XLA gives
the MXU the cache's own bf16 keys and values, a query scaled by
`1/sqrt(D)` in float32 and rounded to bf16, and float32 weights that
the MXU's single default pass rounds to bf16 (read from the optimized
HLO of the `[1, 2048]` prefill, PERF.md section 6, PR 37). So the
caller scales the query in float32 and casts it to the operands'
dtype, both products take operands of that dtype (the wider of the
query's and the cache's) and accumulate in float32, and `m`, `l` and
the accumulator are float32; the output is cast to the query's dtype
at the end. The online softmax reorders a sum and normalises after
the second product, not before, so outputs are not bit-identical to
the gather's: float32 parameters and cache stay within 2e-5 of the
gather oracle at model level (tests/test_pallas_kernels.py), and with
bf16 each path is held to a float32 reference, as the tick's kernel
is.

On the CPU backend the kernel runs through the Pallas interpreter; on
a TPU it is compiled, and any other backend is refused.
`tests/test_tpu_compile.py` compiles it for a described v5e at the
benchmark cells' geometries.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.attention import NEG_INF
from hyperion_tpu.ops.pallas.backend import (
    LANES,      # softmax statistics are carried lane-replicated
    cost,
    interpret_on_backend,
)

# The VMEM plan (`_plan`). A step holds its query tile and its output
# tile (double-buffered by the pipeline), a key and a value tile (the
# same), the statistics and the accumulator, and the score tile's
# float32 temporaries, of which the account keeps three (scores,
# exponentials, the cast for the second product). `_ROWS` and `_KEYS`
# are the tile the plan aims at: 256 positions at 4, 6 and 7 query
# heads a KV head and 1024 keys, the fastest of the probe on a v5e at
# every cell's shape (PERF.md section 6, PR 37: 512 keys 1.5-1.7 times
# slower at long chains, because every tile pays a pass over the
# lane-replicated statistics and the accumulator that is worth 512
# keys of a score tile; 2048 keys 5-30 % slower, more of a tile on the
# diagonal being masked work). `_VMEM_BUDGET` is what a plan may take;
# the call asks the compiler for `_VMEM_LIMIT`, above the 16 MiB it
# scopes a kernel to by default and far under a v5e's 128 MiB.
_ROWS = 1792
_KEYS = 1024
_VMEM_BUDGET = 32 << 20
_VMEM_LIMIT = 48 << 20


def _interpret() -> bool:
    return interpret_on_backend()


def plan_vmem_bytes(tq: int, tk: int, rep: int, D: int, q_itemsize: int,
                    kv_itemsize: int) -> int:
    """What a step of `(tq, tk)` tiles keeps in VMEM, by the account
    above."""
    rows = tq * rep
    return (4 * rows * D * q_itemsize           # query in, output out, x2
            + 4 * tk * D * kv_itemsize          # key and value, x2
            + 2 * rows * LANES * 4              # m, l
            + rows * D * 4                      # accumulator
            + 3 * rows * tk * 4)                # score temporaries


def _plan(T: int, rep: int, D: int, bs: int, view_blocks: int,
          q_dtype, kv_dtype) -> tuple[int, int]:
    """(query positions, keys) a tile for a call's static shape: about
    `_ROWS` rows of `rep` heads a position and `_KEYS` keys, in whole
    blocks of the cache, no longer than the window and the view, and
    halved, the score tile's longer side first, until the step fits
    `_VMEM_BUDGET`."""
    sizes = (jnp.promote_types(q_dtype, kv_dtype).itemsize,
             jnp.dtype(kv_dtype).itemsize)
    # a power of two of positions: whole sublane tiles of rows at any
    # `rep`, and every bucket and chunk is a multiple of it
    tq = 1 << max(4, (max(1, _ROWS // rep)).bit_length() - 1)
    tq = min(tq, 1 << max(4, (T - 1).bit_length()))
    # whole blocks and whole lanes of keys
    unit = bs * LANES // math.gcd(bs, LANES)
    tk = max(unit, _KEYS // unit * unit)
    tk = min(tk, -(-view_blocks * bs // unit) * unit)
    while plan_vmem_bytes(tq, tk, rep, D, *sizes) > _VMEM_BUDGET:
        if tq > 16 and (tq * rep >= tk or tk == unit):
            tq //= 2
        elif tk > unit:
            tk = max(unit, tk // 2 // unit * unit)
        else:
            break
    return tq, tk


def _tile_span(base, view0, qi, *, tq, tk, nk, window):
    """(first, last) key tile the queries of query tile `qi` can see:
    the tile of the last query's own position, and of a windowed layer
    the tile of the first position the first query's window holds;
    both inside the view, and never empty."""
    q_lo = base + qi * tq
    hi = jnp.clip((q_lo + tq - 1 - view0) // tk, 0, nk - 1)
    if not window:
        return jnp.int32(0), hi
    return jnp.clip((q_lo - window + 1 - view0) // tk, 0, hi), hi


def _window_kernel(base_ref, view0_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, tq, tk, rep, window, keys):
    """One (row, KV head, query tile, key tile) step.

    q_ref/o_ref [tq * rep, D]: the head's scaled query group of `tq`
    positions (row r is position r // rep) and its output. k_ref/v_ref
    [tk // bs, bs, D]: `tk` keys of the view, in whole blocks. m_ref,
    l_ref [tq * rep, LANES], acc_ref [tq * rep, D]: float32 running
    max, sum and accumulator. `keys`: the view's own length where the
    caller padded it to whole tiles, else 0."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    rows, D = q_ref.shape
    base, view0 = base_ref[b], view0_ref[b]
    lo, hi = _tile_span(base, view0, qi, tq=tq, tk=tk, nk=nk,
                        window=window)
    q_lo = base + qi * tq
    k_lo = view0 + ki * tk

    @pl.when(ki == 0)
    def _first():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        q = q_ref[...]
        k = k_ref[...].reshape(tk, D).astype(q.dtype)
        v = v_ref[...].reshape(tk, D).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, tk]
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) // rep
            kv_pos = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, tk), 1)
            seen = kv_pos <= q_pos
            if window:
                seen &= kv_pos > q_pos - window
            if keys:
                seen &= kv_pos < view0 + keys
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]                                 # [rows, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    needed = (ki >= lo) & (ki <= hi)
    # every query of the tile sees every key of the tile
    whole = k_lo + tk - 1 <= q_lo
    if window:
        whole &= k_lo > q_lo + tq - 1 - window
    if keys:
        whole &= k_lo + tk <= view0 + keys

    @pl.when(needed & whole)
    def _inside():
        step(masked=False)

    @pl.when(needed & jnp.logical_not(whole))
    def _edge():
        step(masked=True)

    @pl.when(ki == nk - 1)
    def _last():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def window_attention(q, k_blocks, v_blocks, base, view0, *,
                     window: int = 0, keys: int = 0,
                     tile_q: int | None = None, tile_k: int | None = None):
    """Masked grouped attention of a prompt window over a gathered
    chain.

    Args:
      q: [B, T, H, D] query window, rotary applied, not scaled.
      k_blocks, v_blocks: [B, NBv, Hkv, bs, D], each row's view of its
        chain in whole blocks (`pool[table]`), the window's own keys
        written; `NBv * bs` a multiple of the key tile.
      base: [B] int32 first logical position of the window per row.
      view0: [B] int32 logical position of each view's first key.
      window: 0 for a full layer; a windowed layer's size: a query at p
        sees keys `p - window < j <= p`.
      keys: the view's own length where the caller padded it beyond to
        reach whole tiles (the padding is masked), else 0.
      tile_q, tile_k: query positions and keys a tile, in place of the
        plan's (the probe and the tests set them).

    Returns [B, T, H, D] in q's dtype.
    """
    B, T, H, D = q.shape
    Hkv = k_blocks.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    if v_blocks.shape != k_blocks.shape:
        raise ValueError(
            f"view shapes differ: {k_blocks.shape} vs {v_blocks.shape}")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if k_blocks.shape[0] != B or base.shape != (B,) or view0.shape != (B,):
        raise ValueError(
            f"batch mismatch: q {B}, view {k_blocks.shape}, "
            f"base {base.shape}, view0 {view0.shape}")
    NBv, bs = k_blocks.shape[1], k_blocks.shape[3]
    tq, tk = _plan(T, H // Hkv, D, bs, NBv, q.dtype, k_blocks.dtype)
    tq, tk = tile_q or tq, tile_k or tk
    if tk % bs or (NBv * bs) % tk:
        raise ValueError(
            f"a view of {NBv} blocks of {bs} is not whole tiles of {tk} "
            "keys: pad the table to `view_tile` multiples")
    return _window_attention(
        q, k_blocks, v_blocks, jnp.asarray(base, jnp.int32),
        jnp.asarray(view0, jnp.int32), window=int(window), keys=int(keys),
        tq=tq, tk=tk, interpret=_interpret())


def view_tile(T: int, rep: int, D: int, bs: int, view_blocks: int,
              q_dtype, kv_dtype) -> int:
    """Keys a tile the plan gives a call: what the caller pads its view
    to whole multiples of."""
    return _plan(T, rep, D, bs, view_blocks, q_dtype, kv_dtype)[1]


# A jit of its own, as the tick's kernel has: a model calls the kernel
# once a layer with the same shapes, and under one jit the calls share
# one trace of the body and one lowering to a Mosaic module. The tiles
# are the plan's, made outside and static here.
@functools.partial(
    jax.jit, static_argnames=("window", "keys", "tq", "tk", "interpret"))
def _window_attention(q, k_blocks, v_blocks, base, view0, *, window, keys,
                      tq, tk, interpret):
    B, T, H, D = q.shape
    _, NBv, Hkv, bs, _ = k_blocks.shape
    rep = H // Hkv
    op_dtype = jnp.promote_types(q.dtype, k_blocks.dtype)
    nq, nk = -(-T // tq), NBv * bs // tk
    Tp = nq * tq
    # scaled in float32 and cast to the operands' dtype: what XLA hands
    # the MXU on the gather path. [B, T, H, D] -> [B, Hkv, T*rep, D]:
    # a step sees a KV head's whole query group; row r is token r // rep
    qg = (q.astype(jnp.float32) * (1.0 / np.sqrt(D))).astype(op_dtype)
    qg = (
        qg.reshape(B, T, Hkv, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, T * rep, D)
    )
    if Tp != T:
        # ordinary rows of the same mask at later positions, sliced off
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, (Tp - T) * rep), (0, 0)))
    span = functools.partial(_tile_span, tq=tq, tk=tk, nk=nk, window=window)

    def q_map(b, h, qi, ki, base_ref, view0_ref):
        return b, h, qi, 0

    def kv_map(b, h, qi, ki, base_ref, view0_ref):
        lo, hi = span(base_ref[b], view0_ref[b], qi)
        return b, jnp.clip(ki, lo, hi), h, 0, 0

    q_block = pl.BlockSpec((None, None, tq * rep, D), q_map)
    kv_block = pl.BlockSpec((None, tk // bs, None, bs, D), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nq, nk),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((tq * rep, LANES), jnp.float32),
            pltpu.VMEM((tq * rep, LANES), jnp.float32),
            pltpu.VMEM((tq * rep, D), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((B, Hkv, Tp * rep, D), q.dtype)
    # The static worst case of the mask: under the diagonal of a full
    # layer half the (query, key) pairs of a window that ends at the
    # view's end; of a windowed layer `window` keys a query. What a call
    # does follows `base`, which no static estimate can see.
    pairs = B * H * T * (min(window, NBv * bs) if window
                         else NBv * bs - T // 2)
    out = pl.pallas_call(
        functools.partial(_window_kernel, tq=tq, tk=tk, rep=rep,
                          window=window, keys=keys),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        cost_estimate=cost(
            4 * pairs * D, pairs, qg, out_shape, k_blocks, v_blocks,
            base, view0),
    )(base, view0, qg, k_blocks, v_blocks)
    return (
        out[:, :, :T * rep].reshape(B, Hkv, T, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, T, H, D)
    )
