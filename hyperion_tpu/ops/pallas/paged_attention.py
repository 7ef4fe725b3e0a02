"""Paged decode attention: the kernel that reads the KV pool in place.

Role in the stack (ROADMAP S3, vLLM §4): the gather read path of
`models/llama.py` copies `pool[block_tables]` into a contiguous view
every decode tick, copies that view into another layout and reads it a
third time as float32: every table column of every slot, mapped or not,
moved several times over before one product runs. This kernel reads the
pools where they lie, and only each slot's LIVE blocks: a call's bytes
follow the tokens the slots hold, not the table's width.

The walk:

  * Grid `(S, Hkv // Hg)`: one step is one slot and `Hg` of its KV
    heads; at decode and verify widths `Hg = Hkv`, so a call of 48
    slots is 48 steps. (The kernel this replaces ran one 4 KB tile a
    step, 49,152 steps a call, and was bound by step overhead: PERF.md
    section 6, PR 24.)
  * The pools stay in HBM (`memory_space=pl.ANY`). Inside a step a loop
    walks the slot's chain from block `lo` to `ceil((base + T) / bs)`,
    both read from the scalar-prefetched `base`, and stops there:
    unmapped table columns are never fetched. `lo` is 0 for a full
    layer (`window` 0) and the block of the first position the slot's
    first query can see for a windowed one,
    `max(base - window + 1, 0) // bs`: the table stays indexed by
    logical block, and the blocks the engine has let go (null entries
    before `lo`) are never fetched either.
  * One DMA a block and pool: a block `[Hkv, bs, D]` is contiguous in
    the pool's layout (32 KB at Mistral's widths), so one descriptor
    moves all its heads; it lands head-major in a VMEM scratch
    `[Hg, G * bs, D]`, where each head's keys of the whole group are
    one contiguous operand. The DMAs go out `G` blocks (a group) at a
    time into one of two buffers, and the next group (the slot's next,
    or the NEXT step's first) is in flight while this one is
    multiplied. Which buffer a step starts in rides an SMEM scratch
    across steps, so the grid is "arbitrary" (sequential).
  * Every head of a fetched group is consumed from VMEM: the slot's
    regrouped query window `[Hg, T * rep, D]` rides in whole, and an
    online softmax carries `m`, `l` (lane-replicated) and the
    accumulator per head across groups.
  * `_plan` chooses `Hg` and `G` from the call's static shape under a
    VMEM budget: prompt-length windows get fewer heads a step and
    smaller groups; they compile and are right, but the model does not
    send them here (`select_paged_attn_impl`): a prompt window gathers
    its slot's chain once and reads it through the tiled kernel of
    `window_attention.py`, or, where it is narrow, through the
    gather's one-shot softmax (measured against this walk at the
    cells' shapes: PERF.md section 6, PR 37).

Masking contract, identical to the gather path: kv position `p`
attends iff `p <= base[b] + row // rep` (per-row causal frontier over
the filled prefix) and, in a windowed layer, `p > base[b] + row // rep
- window`. Positions past the frontier inside the last live
block, and whatever a buffer holds beyond the live blocks of a short
group, get weight exactly 0 (finite NEG_INF); both buffers are zeroed
once, before the first DMA, so nothing uninitialised is ever
multiplied. The null block 0 is fetched only where a table names
it at or after `lo` and at or before the frontier, which no live
slot's table does; an inactive lane (an all-null table, whatever
length its last occupant left in `base`) walks one block of it and its
row is ignored by the caller.

Numerics: operands are cast to float32 in VMEM, products accumulate in
float32, softmax statistics and the accumulator are float32, matching
`_grouped_cache_attention`'s float32 einsums. The online softmax
reorders the reduction, so outputs are NOT bit-identical to the
gather path's one-shot softmax. Model-level bounds vs the gather
oracle: fp32 params+cache <= 2e-5 abs/rel on the 2-layer d=64 test
model (tests/test_pallas_kernels.py; ~1e-7 at kernel level). With bf16
compute and a bf16 cache no fixed bound carries across sizes: on a v5e
at 7B widths and 16 layers the two paths' logits differ by up to 0.25
(std 1.28) while each sits the same 5.2 % RMS from an fp32 reference
(chip_smoke.py, PERF.md PR 21); greedy streams part at near-ties, so
each path is held to the reference, not to the other. `-inf` masking
would make NaN in the rescale (`exp(-inf - -inf)`); NEG_INF is finite.

On the CPU backend the kernel runs through the Pallas interpreter,
DMAs and semaphores included, so tier-1 exercises the real walk; on a
TPU it is compiled, and any other backend is refused.
`tests/test_tpu_compile.py` compiles it for a described v5e at the
benchmark cells' geometries (Mistral's tick; Trinity's, full and
windowed) and in all three engine window shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.attention import NEG_INF, window_view_blocks
from hyperion_tpu.ops.pallas.backend import (
    LANES,      # softmax statistics are carried lane-replicated
    SUBLANES,   # query rows are padded to a whole fp32 sublane tile
    cost,
    interpret_on_backend,
)

# The VMEM plan (`_plan`): what a step's resident blocks (query window
# in and out, double-buffered by the pipeline; accumulator; statistics)
# may take, what the two pools' double buffers may take, and how large
# one head's score tile may grow before a group is cut. 8 MB of buffers
# are groups of 64 blocks at Mistral's widths, the fastest measured on
# a v5e (PERF.md section 6, PR 27: 0.40-0.44 ms a call at 48 slots half
# filled; groups of 32 0.48-0.52, of 16 and of 8 0.67-0.68): the DMAs
# bound the kernel, not the products, and a longer group keeps more of
# them in flight.
_STEP_BUDGET = 4 << 20
_KV_BUDGET = 8 << 20
_SCORE_BUDGET = 256 << 10


def _interpret() -> bool:
    return interpret_on_backend()


def _plan(rows_p: int, Hkv: int, bs: int, D: int, itemsize: int,
          walk: int) -> tuple[int, int]:
    """(KV heads a grid step, blocks a DMA group) for a call's static
    shape; `walk` is the most blocks a slot's walk spans. Decode and
    verify windows take every head in one step and groups of 64 blocks
    at Mistral's widths; a prompt-length window takes fewer heads and
    smaller groups so its step stays in VMEM."""
    per_head = rows_p * D * (4 * itemsize + 4) + 2 * rows_p * LANES * 4
    Hg = max(h for h in range(1, Hkv + 1)
             if Hkv % h == 0 and (h == 1 or h * per_head <= _STEP_BUDGET))
    G = min(walk,
            _KV_BUDGET // (4 * Hg * bs * D * itemsize),
            _SCORE_BUDGET // (rows_p * bs * 4))
    return Hg, max(1, G)


def _walk_kernel(bt_ref, base_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref,
                 *, bs, mb, rep, t, hg, G, window):
    """One (slot, head group) step: walk the slot's live blocks a group
    at a time.

    q_ref/o_ref [hg, rows_p, D]: the slot's regrouped query window of
    these heads (row r is token r // rep) and its output. k_hbm/v_hbm
    [NB, Hkv, bs, D]: the pools, in HBM. k_buf/v_buf [2, hg, G*bs, D]:
    the two group buffers; sems [2 (k, v), 2 (buffer)]. slot_ref [1]
    (SMEM): the buffer this step's first group was fetched into.
    m_ref/l_ref [hg, rows_p, LANES], acc_ref [hg, rows_p, D]: float32
    running max, sum and accumulator per head."""
    b, c = pl.program_id(0), pl.program_id(1)
    S, NC = pl.num_programs(0), pl.num_programs(1)
    D = q_ref.shape[-1]

    def span(slot_b):
        """(first, one past the last) block of a slot's walk: from the
        chain's block 0 in a full layer, from the block that holds the
        first position the slot's first query sees in a windowed one,
        to the block of the window's last position."""
        hi = (base_ref[slot_b] + t + bs - 1) // bs
        lo = 0
        if window:
            lo = jnp.clip(
                (base_ref[slot_b] - window + 1) // bs, 0, mb - 1)
        # a lane the tick masks out keeps its last occupant's length
        # over an all-null table: one block of it, not a stale chain.
        # (A live slot's entry `lo` is mapped: the engine lets go only
        # the blocks wholly behind the window of the next query.)
        hi = jnp.where(bt_ref[slot_b, lo] == 0, lo + 1, hi)
        return lo, jnp.clip(hi, lo + 1, mb)

    def group_copies(slot_b, heads, j0, hi, buf, fn):
        """`fn` (start or wait) on the DMAs of blocks j0..min(j0+G, hi)
        of slot `slot_b`'s chain into buffer `buf`."""
        def one(i, _):
            phys = bt_ref[slot_b, j0 + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for kv, (hbm, vm) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                fn(pltpu.make_async_copy(
                    hbm.at[phys, heads],
                    vm.at[buf, :, rows, :],
                    sems.at[kv, buf]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(hi - j0, G), one, 0)

    def heads_of(ci):
        return pl.ds(pl.multiple_of(ci * hg, hg), hg)

    @pl.when((b == 0) & (c == 0))
    def _first():
        # nothing uninitialised may meet a zero weight, or the query of
        # a padded row or of an inactive lane: a slot shorter than a
        # group leaves the rest of its buffer as it found it
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        lo0, hi0 = span(0)
        group_copies(0, heads_of(0), lo0, hi0, 0,
                     lambda cp: cp.start())

    lo, hi = span(b)
    ng = (hi - lo + G - 1) // G
    buf0 = slot_ref[0]
    # the step after this one: the slot's next heads, or the next slot
    wraps = c + 1 == NC
    nb = jnp.where(wraps, jnp.minimum(b + 1, S - 1), b)
    nc = jnp.where(wraps, 0, c + 1)
    more = jnp.logical_not(wraps & (b + 1 == S))
    nlo, nhi = span(nb)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    base = base_ref[b]
    scale = 1.0 / np.sqrt(D)

    def group(g, _):
        buf = (buf0 + g) % 2
        j0 = lo + g * G

        @pl.when(g + 1 < ng)
        def _next_group():
            group_copies(b, heads_of(c), j0 + G, hi, 1 - buf,
                         lambda cp: cp.start())

        @pl.when((g + 1 == ng) & more)
        def _next_step():
            group_copies(nb, heads_of(nc), nlo, nhi, 1 - buf,
                         lambda cp: cp.start())

        group_copies(b, heads_of(c), j0, hi, buf, lambda cp: cp.wait())

        def head(h, _):
            q = q_ref[h].astype(jnp.float32) * scale        # [rows_p, D]
            k = k_buf[buf, h].astype(jnp.float32)           # [G*bs, D]
            v = v_buf[buf, h].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows_p, G*bs]
            q_pos = base + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // rep
            kv_pos = j0 * bs + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            seen = kv_pos <= q_pos
            if window:
                seen &= kv_pos > q_pos - window
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[h]                               # [rows_p, LANES]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new
            return 0

        jax.lax.fori_loop(0, hg, head, 0, unroll=True)
        return 0

    jax.lax.fori_loop(0, ng, group, 0)
    slot_ref[0] = (buf0 + ng) % 2
    # l > 0 always: a full layer's walk starts at block 0, whose first
    # position every query row sees; a windowed walk starts at block
    # `lo`, which holds the first position of the first query's window,
    # `max(base - window + 1, 0)`. A later token's window may start
    # beyond a short first group: its sums there are over masked scores
    # only, and the first group it does see (its own position lies
    # inside the walk) rescales them by exp(NEG_INF - m) = 0. Padded
    # rows are ordinary rows of the same mask, sliced off by the caller.
    o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, base, *,
                    window: int = 0, blocks_per_group: int | None = None):
    """Decode attention straight against the paged KV pools.

    Args:
      q: [B, T, H, D] query window (T = 1 decode, k+1 verify, or C
        chunk), rotary already applied.
      k_pool, v_pool: [num_blocks, Hkv, block_size, D] pooled cache,
        with the current window's K/V already scattered in (the caller
        writes before attending, as the gather path does).
      block_tables: [B, MB] int32 physical-block chain per slot;
        unmapped tail entries are 0 (the null block).
      base: [B] int32 first logical position of the window per slot.
      window: 0 for a full layer; a windowed layer's size: a query at p
        sees keys `p - window < j <= p`, and the walk starts at the
        block of `base - window + 1`. The table is still indexed by
        logical block; entries before that block may be null.
      blocks_per_group: blocks a DMA group, in place of the plan's
        (tests walk several groups of a short table with it).

    Returns [B, T, H, D] in q's dtype.
    """
    B, T, H, D = q.shape
    Hkv = k_pool.shape[1]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    if v_pool.shape != k_pool.shape:
        raise ValueError(f"pool shapes differ: {k_pool.shape} vs {v_pool.shape}")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if block_tables.shape[0] != B or base.shape != (B,):
        raise ValueError(
            f"table/base batch mismatch: q {B}, "
            f"tables {block_tables.shape}, base {base.shape}"
        )
    return _paged_attention(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(base, jnp.int32), window=int(window),
        blocks_per_group=blocks_per_group, interpret=_interpret())


# A jit of its own: a model calls the kernel once a layer with the same
# shapes, and under one jit the calls share one trace of the kernel's
# body and one lowering of it to a Mosaic module. Traced and lowered
# inline, sixteen layers' calls added 6 s to every start of the server,
# cached executable or not (PERF.md section 6, PR 27). A model whose
# layers are of two kinds holds two: `window` is static.
@functools.partial(
    jax.jit, static_argnames=("window", "blocks_per_group", "interpret"))
def _paged_attention(q, k_pool, v_pool, block_tables, base, *,
                     window, blocks_per_group, interpret):
    B, T, H, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    rep = H // Hkv
    rows = T * rep
    rows_p = -(-rows // SUBLANES) * SUBLANES
    # the most blocks one slot's walk spans: the table's width, or of a
    # windowed layer what a window can span (a group is never longer)
    walk = min(MB, window_view_blocks(window, T, bs)) if window else MB
    Hg, G = _plan(rows_p, Hkv, bs, D, k_pool.dtype.itemsize, walk)
    G = blocks_per_group or G
    # [B, T, H, D] -> [B, Hkv, T*rep, D]: a step sees each KV head's
    # whole query group; row r is token r // rep.
    qg = (
        q.reshape(B, T, Hkv, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, rows, D)
    )
    if rows_p != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
    q_block = pl.BlockSpec(
        (None, Hg, rows_p, D),
        lambda b, c, bt_ref, base_ref: (b, c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // Hg),
        in_specs=[
            q_block,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((2, Hg, G * bs, D), k_pool.dtype),
            pltpu.VMEM((2, Hg, G * bs, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hg, rows_p, LANES), jnp.float32),
            pltpu.VMEM((Hg, rows_p, LANES), jnp.float32),
            pltpu.VMEM((Hg, rows_p, D), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((B, Hkv, rows_p, D), q.dtype)
    # The static worst case, every table entry live (of a windowed
    # layer: every entry a window can span): a block of K and of V once
    # per entry, two products of rows x bs x D per block and head, one
    # exponential per score. What a call moves follows the slots'
    # lengths (`base`), which no static estimate can see.
    chain = B * walk * Hkv * bs
    out = pl.pallas_call(
        functools.partial(_walk_kernel, bs=bs, mb=MB, rep=rep, t=T,
                          hg=Hg, G=G, window=window),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # a step hands the next its first group in flight and the
        # buffer it lies in: the steps run in order
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        cost_estimate=cost(
            4 * chain * rows * D, chain * rows,
            qg, out_shape, block_tables, base,
            extra_bytes=2 * chain * D * k_pool.dtype.itemsize),
    )(block_tables, base, qg, k_pool, v_pool)
    return (
        out[:, :, :rows].reshape(B, Hkv, T, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, T, H, D)
    )
