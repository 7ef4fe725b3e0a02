"""Paged decode attention — the Pallas kernel that kills the KV gather.

Role in the stack (ROADMAP item 1, vLLM §4): the paged branch of
`models/llama.py` historically materialized `pool[block_tables]` into a
contiguous `[B, L, Hkv, D]` view every decode tick, so each generated
token paid an HBM round trip over the slot's ENTIRE mapped KV chain —
2 * S * MB * block_size * Hkv * D * itemsize bytes per layer per tick —
before a single FLOP of attention ran. That copy exists only to satisfy
`_grouped_cache_attention`'s contiguous-layout expectation. This kernel
reads the pools in place instead: the `[S, MB]` block table rides in as
a scalar-prefetch operand, and the BlockSpec index_map of the K/V pool
operands dereferences it per grid step, so the DMA engine fetches each
mapped `[block_size, D]` tile straight from its pooled home.

Design:

  * Grid `(S, Hkv, MB)` — one program per (slot, KV-head group), the MB
    axis innermost and marked "arbitrary": the block sweep for one slot
    revisits VMEM scratch (m, l, acc) with the classic online-softmax
    recurrence, finalizing `o = acc / l` on the last block. VMEM holds
    one `[block_size, D]` K/V tile pair at a time.
  * Block-table walk: `pltpu.PrefetchScalarGridSpec` with
    `num_scalar_prefetch=2` (block table + per-slot base positions).
    Scalar-prefetch refs are visible to index_maps, so the pool specs
    map grid step `(b, g, j)` to physical block `bt_ref[b, j]` — the
    data-dependent indexing the plain BlockSpec grid cannot express.
  * GQA rides inside the program: q `[B, T, H, D]` is regrouped to
    `[B, Hkv, T*rep, D]` so one program handles a whole query-head
    group; the flattened row r corresponds to token `r // rep`, which
    is all the masking needs to know.
  * Masking contract — identical to the gather path: kv position
    `j*bs + col` attends iff `<= base[b] + row//rep` (per-row causal
    frontier over the filled prefix). Beyond-length positions and the
    serve engine's null block 0 (where unmapped/bucket-padding
    positions scatter) are thereby invisible: every block-table entry
    at or before the frontier is a real mapped block, and everything
    after is masked. Blocks that start wholly past the frontier are
    skipped outright (`pl.when`) — the win that makes short sequences
    in deep tables cheap.
  * One compiled executable serves all three engine geometries —
    sequential decode `[S, 1]`, speculative verify `[S, k+1]`, chunked
    prefill `[1, C]` — because geometry only changes static shapes the
    engine already buckets; table contents and bases are runtime data
    and never retrace.

Numerics: matmuls run fp32-accumulated (`preferred_element_type`);
softmax statistics and the output accumulator are fp32, matching
`_grouped_cache_attention`'s fp32 einsum math. The online softmax
reorders the reduction, so outputs are NOT bit-identical to the
one-shot softmax of the gather path. Model-level bounds vs the gather
oracle: fp32 params+cache <= 2e-5 abs/rel on the 2-layer d=64 test
model (asserted in tests/test_pallas_kernels.py; ~1e-7 at kernel
level). With bf16 compute and a bf16 cache no fixed bound carries
across sizes: on a v5e at Llama-2-7B widths and 16 layers the two
paths' logits differ by up to 0.25 (std 1.28) while each sits the same
5.2 % RMS from an fp32 reference forward (chip_smoke.py, PERF.md PR 21)
— that is bf16 rounding carried through the depth, and greedy token
streams of the two paths part at near-ties. chip_smoke.py therefore
holds each path to the reference, not to the other. Masked logits use
the shared finite NEG_INF — `-inf` would produce NaN via
`exp(-inf - -inf)` in the rescale when a row's first visited block is
fully masked.

On the CPU backend the kernel runs in interpret mode (same posture
as flash_attention.py), so tier-1 exercises the real block walk; on a
TPU it is compiled, and any other backend is refused.

TPU compile note: the pools are `[num_blocks, Hkv, block_size, D]`, so
the pool BlockSpec `(None, None, bs, D)` covers the array's last two
dims whole (block dim == array dim is legal whatever the (8, 128)
tiling) and squeezes the physical-block and KV-head axes out of the
kernel refs. With `Hkv` behind `block_size`, as the pools once were,
the squeezed head axis was the second-to-last array dim and Mosaic
refused the block at every Llama width. The softmax statistics are
carried lane-replicated `[rows, 128]` (a 1-D `(rows,)` scratch has no
agreed tiling at rows=1), and the query window is padded to a whole
sublane tile of rows. `tests/test_tpu_compile.py` compiles MHA and GQA
widths for a described v5e in all three geometries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.attention import NEG_INF
from hyperion_tpu.ops.pallas.backend import (
    LANES,      # softmax statistics are carried lane-replicated
    SUBLANES,   # query rows are padded to a whole fp32 sublane tile
    cost,
    interpret_on_backend,
)

# Performance-relevant revision, stamped into the decode_attention bench
# probe rows so offline readers can tell a capture of THIS kernel from a
# stale one. Bump on any change that moves measured throughput.
KERNEL_REV = 2


def _interpret() -> bool:
    return interpret_on_backend()


def _compiler_params():
    if _interpret():
        return None
    # Slot and group programs are independent; the block sweep carries
    # the online-softmax scratch and must run in order.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _decode_kernel(bt_ref, base_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs, mb, rep, t):
    """One (slot, group) program; grid step j sweeps the slot's blocks.

    q_ref [rows_p, D] is the slot's whole regrouped query window
    (T * rep rows, padded to a sublane multiple); k_ref/v_ref [bs, D]
    is physical block `bt_ref[b, j]` of this group's pool, DMA'd in by
    the index_map. m_ref/l_ref [rows_p, LANES] hold each row's running
    max and sum replicated across the lanes."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = base_ref[b]
    # Skip blocks that start past the deepest query position
    # base + T - 1 — unmapped (null-block) table entries all live there.
    relevant = j * bs <= base + (t - 1)

    @pl.when(relevant)
    def _update():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, bs]
        q_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]                                   # [rows, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == mb - 1)
    def _done():
        # l > 0 always: at j == 0, kv position 0 satisfies the mask for
        # every query row (q_pos = base + t >= 0), so the first visited
        # block contributes at least one unmasked column per row.
        # (padded rows, base + row//rep beyond the window, are ordinary
        # causal rows and sliced off by the caller)
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, base):
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

    Returns [B, T, H, D] in q's dtype.
    """
    B, T, H, D = q.shape
    Hkv = k_pool.shape[1]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    if v_pool.shape != k_pool.shape:
        raise ValueError(f"pool shapes differ: {k_pool.shape} vs {v_pool.shape}")
    if block_tables.shape[0] != B or base.shape != (B,):
        raise ValueError(
            f"table/base batch mismatch: q {B}, "
            f"tables {block_tables.shape}, base {base.shape}"
        )
    rep = H // Hkv
    bs = k_pool.shape[2]
    MB = block_tables.shape[1]
    rows = T * rep
    rows_p = -(-rows // SUBLANES) * SUBLANES
    # [B, T, H, D] -> [B, Hkv, T*rep, D]: one program per KV-head group
    # sees its whole query group; row r is token r // rep.
    qg = (
        q.reshape(B, T, Hkv, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, Hkv, rows, D)
    )
    if rows_p != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, MB),
        in_specs=[
            pl.BlockSpec(
                (None, None, rows_p, D),
                lambda b, g, j, bt_ref, base_ref: (b, g, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, bs, D),
                lambda b, g, j, bt_ref, base_ref: (bt_ref[b, j], g, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, bs, D),
                lambda b, g, j, bt_ref, base_ref: (bt_ref[b, j], g, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, rows_p, D),
            lambda b, g, j, bt_ref, base_ref: (b, g, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((rows_p, LANES), jnp.float32),
            pltpu.VMEM((rows_p, LANES), jnp.float32),
            pltpu.VMEM((rows_p, D), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((B, Hkv, rows_p, D), q.dtype)
    # every table entry's block of K and of V is read once (unmapped
    # entries read the null block), never the pools whole; two products
    # of rows x bs x D per block, one exponential per score
    chain = B * MB * Hkv * bs
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, mb=MB, rep=rep, t=T),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        cost_estimate=cost(
            4 * chain * rows * D, chain * rows,
            qg, out_shape, block_tables, base,
            extra_bytes=2 * chain * D * k_pool.dtype.itemsize),
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(base, jnp.int32),
      qg, k_pool, v_pool)
    return (
        out[:, :, :rows].reshape(B, Hkv, T, rep, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, T, H, D)
    )
