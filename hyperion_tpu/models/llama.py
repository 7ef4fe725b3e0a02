"""Llama-2 architecture — the 7B fine-tuning workload, in-tree.

Reference: `distributed_utils.py:465-467,484-487` loads HF
`NousResearch/Llama-2-7b-hf` (`AutoModelForCausalLM`) and fine-tunes it
with LoRA+DDP or FSDP. The architecture there lives inside the
`transformers` dependency; here it is implemented in-tree (SURVEY §7.3:
architecture-true implementation + random-init path so training
mechanics and throughput are measurable without the 34 GB of weights,
plus a loader for real checkpoints when present on disk).

Architecture facts (Llama-2-7B): RMSNorm(eps 1e-5), rotary position
embeddings, MHA 32 heads (no GQA at 7B), SwiGLU MLP (gate/up 11008),
32 layers, d 4096, vocab 32000, untied embeddings, context 4096.

TPU-first notes:
  * [B, T, H, D] attention layout shared with every other model — the
    Pallas kernel and ring-attention sharding apply here unchanged.
  * RoPE is computed in fp32 and applied in compute dtype (bf16 rotary
    is a known quality bug in long contexts).
  * Module names (q_proj/…/gate_proj/up_proj/down_proj/embed_tokens/
    lm_head) line up with `parallel.TRANSFORMER_TP_RULES`, so the same
    TP/FSDP rule table shards Llama with no extra code — and they match
    HF weight names, making the checkpoint loader a rename-free walk.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from hyperion_tpu.ops.attention import (
    dot_product_attention,
    window_view_blocks,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32          # 7B has no GQA; kept for 70B-shaped configs
    ff_dim: int = 11008
    max_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attention_impl: str = "xla"
    norm_impl: str = "xla"        # xla | pallas (fused_rmsnorm kernel)
    # Paged-cache read strategy. "gather" materializes
    # pool[block_tables] into a contiguous [B, L, Hkv, D] view (an HBM
    # copy of every table column per call); "pallas" routes through
    # ops.pallas.paged_attention, which walks each row's live blocks
    # in-kernel and reads the pools in place; "auto" resolves per call
    # from its static shape and the backend (`select_paged_attn_impl`:
    # on a TPU that kernel for a few-token window and, for a
    # prompt-length one, "tiled": the gather, then a tiled online
    # softmax over the view, `ops.pallas.window_attention`; the gather
    # otherwise).
    # Identical masking contract; pinned-tolerance numerics (online
    # softmax, see the kernel docstring). Ignored outside the paged
    # (block_tables) path.
    paged_attn_impl: str = "auto"
    # "none" | "int8": weight-only int8 inference (precision/quant.py) —
    # dense kernels become int8+scale (half bf16's HBM traffic, int8
    # MXU matmuls); params come from quantize_params_for() on a trained
    # float checkpoint. Inference-only: train float, then quantize.
    quant: str = "none"
    # Module-level (functional) LoRA: rank > 0 routes the targeted
    # projections through models.lora.LoraDenseGeneral's activation
    # side-path — y = x@W + scale*(x@A)@B — instead of the trainer's
    # weight-delta merge, which at 7B holds ~4 GB of effective-weight
    # remat residuals (the round-4 OOM). Adapter leaves are supplied by
    # lora.structural_merge from the standard {"base","lora"} state.
    lora_rank: int = 0
    lora_scale: float = 2.0   # alpha/r at the peft defaults (32/16)
    lora_targets: tuple[str, ...] = ("q_proj", "k_proj", "v_proj", "o_proj")
    # 7B needs remat on any realistic chip; False/"none", True/"full",
    # or a named precision.remat policy ("dots", "dots_no_batch")
    remat: bool | str = True
    dtype: str = "bfloat16"

    @property
    def remat_policy(self) -> str:
        from hyperion_tpu.precision.remat import normalize_remat

        return normalize_remat(self.remat)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def paged_attn_for(self, window: int) -> str:
        """The paged read ("pallas", "tiled" or "gather") of a call
        whose rows each carry `window` query positions: `paged_attn_impl`, with
        "auto" resolved from that width and the process's backend. The
        attention module asks per call; the engine asks for its tick."""
        if self.paged_attn_impl != "auto":
            return self.paged_attn_impl
        return select_paged_attn_impl(
            window, self.n_heads // self.n_kv_heads, jax.default_backend())

    @property
    def layer_kinds(self) -> tuple[tuple[str, int], ...]:
        """What each layer keeps in the serving cache, `(kind, window)`:
        the engine builds one pool, block manager and block table per
        kind (`init_paged_cache`, serve/engine.py). Every layer here is
        `full`: it keeps every position of a request."""
        return (("full", 0),) * self.n_layers


def llama2_7b_config(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama2_70b_config(**kw) -> LlamaConfig:
    """70B-shaped: the GQA geometry (64 query heads sharing 8 KV heads —
    the attention stack's `rep = n_heads // n_kv_heads` path at its
    intended ratio, and an 8x smaller KV cache at decode). Too big for
    any single chip; pairs with `--dry-init --mesh ...` to plan pod-
    scale FSDP/TP layouts from any box."""
    base = dict(
        d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        ff_dim=28672,
    )
    base.update(kw)
    return LlamaConfig(**base)


def llama_tiny_config(**kw) -> LlamaConfig:
    """Test/bench-sized config with the real op mix."""
    base = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        ff_dim=128, max_len=64, remat=False, dtype="float32",
    )
    base.update(kw)
    return LlamaConfig(**base)


def _dense_ctor(c: LlamaConfig):
    """Llama's dense layers: bias-free, normal(0.02) init, routed
    through the shared quant dispatch (`precision.quant.make_dense`) so
    `c.quant == "int8"` swaps in `QuantDenseGeneral` everywhere, and
    through `LoraDenseGeneral` when `c.lora_rank > 0` (the functional
    LoRA side-path; non-target sites trace as plain dense layers).
    `nn.DenseGeneral(features=int, axis=-1)` is exactly `nn.Dense`
    (same `kernel` leaf name and shape), so checkpoints are unaffected
    by routing everything through one ctor."""
    import functools

    from hyperion_tpu.precision.quant import make_dense

    if c.lora_rank > 0:
        if c.quant != "none":
            raise ValueError("LoRA training and int8 inference quant are "
                             "mutually exclusive (train float, then "
                             "merge + quantize)")
        from hyperion_tpu.models.lora import LoraDenseGeneral

        return functools.partial(
            LoraDenseGeneral, dtype=c.compute_dtype,
            kernel_init=nn.initializers.normal(0.02), use_bias=False,
            lora_rank=c.lora_rank, lora_scale=c.lora_scale,
            lora_targets=tuple(c.lora_targets),
        )
    return make_dense(
        c, kernel_init=nn.initializers.normal(0.02), use_bias=False,
    )


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype
    impl: str = "xla"  # "pallas" → fused single-HBM-pass kernel

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.impl == "pallas":
            from hyperion_tpu.ops.pallas.fused_norm import fused_rmsnorm

            return fused_rmsnorm(x, w, eps=self.eps)
        return rms_norm(x, w, self.eps, self.dtype)


def rms_norm(x, w, eps: float, dtype):
    """`RMSNorm`'s arithmetic on a scale `w` handed in: for a model
    whose layers run inside a `lax` loop over stacked weights, where no
    module can be called."""
    # variance in fp32 (bf16 squares underflow), scale in compute dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return normed * w.astype(dtype)


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> jax.Array:
    """[max_len, head_dim/2] complex-as-(cos,sin) table, fp32."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    t = np.arange(max_len, dtype=np.float32)
    ang = np.outer(t, inv)  # [T, D/2]
    return jnp.asarray(np.stack([np.cos(ang), np.sin(ang)], -1))  # [T, D/2, 2]


def apply_rope(x: jax.Array, table: jax.Array, offset=0) -> jax.Array:
    """Rotate [B, T, H, D] by the fp32 cos/sin table rows
    offset..offset+T (offset may be a traced scalar — decode steps slide
    the window as the KV cache fills — or a [B] vector of per-row
    offsets: the serve engine's slots each sit at their own depth)."""
    T = x.shape[1]
    if getattr(offset, "ndim", 0) >= 1:
        rows = jax.vmap(
            lambda o: jax.lax.dynamic_slice_in_dim(table, o, T, axis=0)
        )(offset)                          # [B, T, D/2, 2]
        cos = rows[..., 0][:, :, None, :]  # [B, T, 1, D/2]
        sin = rows[..., 1][:, :, None, :]
    else:
        rows = jax.lax.dynamic_slice_in_dim(table, offset, T, axis=0)
        cos = rows[:, :, 0][None, :, None, :]  # [1, T, 1, D/2]
        sin = rows[:, :, 1][None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _grouped_cache_attention(q, ck, cv, mask, rep):
    """Decode attention over the KV cache without materializing
    repeated K/V for GQA: the query's head axis folds into (kv_head,
    group) and the group rides the einsum. q [B, T, H, D]; ck/cv
    [B, S, Hkv, D]; mask [T, S] shared across the batch, or [B, T, S]
    per-row (the serve engine's slots each mask to their own filled
    prefix). True = attend.

    In a device trace (obs/xprof.py) the product is `attention`; the
    casts of the cache to float32 are `kv_read`, with the gather and the
    layout copy the paged path makes before calling: together, what it
    costs to put the cache before the product."""
    from hyperion_tpu.ops.attention import NEG_INF

    B, T, H, D = q.shape
    Hkv = ck.shape[2]
    with jax.named_scope("attention"):
        qf = q.astype(jnp.float32).reshape(B, T, Hkv, rep, D)
        scale = 1.0 / np.sqrt(D)
        qf = qf * scale
    with jax.named_scope("kv_read"):
        ckf = ck.astype(jnp.float32)
    with jax.named_scope("attention"):
        logits = jnp.einsum("btgrd,bsgd->bgrts", qf, ckf)
        mask = mask[None, None, None] if mask.ndim == 2 \
            else mask[:, None, None]  # → broadcastable over [B, g, r, T, S]
        logits = jnp.where(mask, logits, NEG_INF)
        weights = jax.nn.softmax(logits, axis=-1)
    with jax.named_scope("kv_read"):
        cvf = cv.astype(jnp.float32)
    with jax.named_scope("attention"):
        out = jnp.einsum("bgrts,bsgd->btgrd", weights, cvf)
        return out.reshape(B, T, H, D).astype(q.dtype)


# The widest query window, in rows of one KV head's group (`T * rep`),
# that "auto" sends to the paged-attention kernel. Set from the two
# shapes measured on a v5e (PERF.md section 6, PR 27): a decode tick
# [48, 1] at rep 4 (4 rows) and a verify window [48, 4] (16 rows), both
# several times faster than the gather. Above it lie prompt-length
# windows ([1, 8] at rep 4 is 32 rows): one slot's chain, gathered once
# for many queries.
PAGED_KERNEL_MAX_ROWS = 16
# The narrowest prompt window, in the same rows, that "auto" sends to
# the tiled kernel over the gathered chain (`paged_tiled_read`). From
# the cells' shapes on a v5e (PERF.md section 6, PR 37): the float32
# scores the gather path writes to HBM are what a wide window costs
# (Mistral's 512 bucket, 2048 rows: 0.17 ms a layer against the
# gather's 0.71; its 2048 bucket 0.53 against 2.34; a 512-position
# chunk of Trinity's full layer 0.28-1.14 against 5.2), and under 1024
# rows (Mistral's buckets up to 128, every bucket of Ouro's at one
# query head a KV head and a view of 768 keys) the two read within
# 0.02 ms of each other. Between the two (the 256 buckets: 0.14 against
# 0.19 at Mistral's) the kernel is a little ahead, but every program
# that holds it lowers its Mosaic module at every start of the server
# (0.3 s of warm-up each on the chip's host), and those buckets are a
# prompt's short last piece: they keep the gather's one-shot softmax.
PAGED_TILED_MIN_ROWS = 2048


def select_paged_attn_impl(window: int, rep: int, backend: str) -> str:
    """Resolve `paged_attn_impl="auto"` to "pallas", "tiled" or
    "gather" for one call, from its static shape (`window` query
    positions a row, `rep` query heads a KV head) and the backend: the
    decode tick and the verify window read the pools in place, a
    prompt-length window gathers its chain and runs the tiled kernel
    over it, and what lies between gathers and takes the one-shot
    softmax. Resolved at trace time, as
    `ops.attention.select_attention_impl` is: jit sees one branch. Off
    a TPU the kernels would run through the interpreter, which is an
    oracle and not a read path."""
    if backend != "tpu":
        return "gather"
    if window * rep <= PAGED_KERNEL_MAX_ROWS:
        return "pallas"
    return "tiled" if window * rep >= PAGED_TILED_MIN_ROWS else "gather"


def _chain_view(pool, block_tables):
    """Each row's block chain gathered out of the pool
    `[NB, Hkv, bs, D]` into the contiguous `[B, MB*bs, Hkv, D]` view
    `_grouped_cache_attention` reads."""
    g = pool[block_tables]                      # [B, MB, Hkv, bs, D]
    B, MB, Hkv, bs, D = g.shape
    return g.swapaxes(2, 3).reshape(B, MB * bs, Hkv, D)


def _kv_write_rows(k_pool, v_pool, k, v, block_tables, base, shift=None):
    """`paged_kv_write` a position at a time: D-wide rows of the pool
    seen as [NB*Hkv*bs, D] (a free reshape), row
    `(phys*Hkv + h)*bs + off`. Right for any window; the grain of the
    windows that fill no block: the decode tick's 48 rows at 48
    unrelated places, a verify window, a bucket under a block. (A
    scatter of [Hkv, D] windows at (phys, :, off) made XLA re-lay out
    the whole pool around it for windows of 2..64 tokens: two
    pool-sized temporaries a call.) A row costs a v5e 68 ns whatever
    it holds, so a 2048-token prompt pays 1.1 ms a pool here (PERF.md
    section 6, PR 32)."""
    T = k.shape[1]
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    L = MB * bs
    cols = base[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    phys = jnp.where(
        cols < L,
        jnp.take_along_axis(
            block_tables, jnp.clip(cols // bs, 0, MB - 1), axis=1),
        jnp.int32(0),
    )
    if shift is not None:
        # after the null routing: an uncovered position lands in the
        # segment's own null block
        phys = phys + shift
    off = cols % bs
    rows = ((phys[:, :, None] * Hkv
             + jnp.arange(Hkv, dtype=jnp.int32)) * bs
            + off[:, :, None])                      # [B, T, Hkv]

    def write(pool, new):
        flat = pool.reshape(-1, pool.shape[-1])
        return flat.at[rows].set(new.astype(pool.dtype)).reshape(
            pool.shape)

    return write(k_pool, k), write(v_pool, v)


def _kv_write_blocks(k_pool, v_pool, k, v, block_tables, base, shift=None):
    """`paged_kv_write` a block at a time, for a window of whole blocks
    (`T % bs == 0`) whose every `base` is a multiple of `bs`: the new
    positions regrouped to the pool's own `[Hkv, bs, D]` blocks and
    scattered along its leading axis, one whole block a table entry
    (128 copies of 32 KiB for a 2048-token prompt at Mistral's widths
    where the rows are 16,384 of 256 B). No window is partial, so the
    pool keeps its layout. Entries the table does not cover or names
    null (bucket padding, a block a windowed layer let go, an inactive
    lane) are dropped, not written to block 0: every index left is a
    block of its own."""
    B, T = k.shape[0], k.shape[1]
    NB, Hkv, bs, D = k_pool.shape
    MB = block_tables.shape[1]
    nb = T // bs
    idx = (base // bs)[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]
    phys = jnp.where(
        idx < MB,
        jnp.take_along_axis(
            block_tables, jnp.clip(idx, 0, MB - 1), axis=1),
        jnp.int32(0),
    ).reshape(-1)                                   # [B * nb]
    # past the pool's end, each at a place of its own: dropped
    phys = jnp.where(
        phys > 0, phys if shift is None else phys + shift,
        NB + jnp.arange(B * nb, dtype=jnp.int32))

    def write(pool, new):
        blocks = (new.astype(pool.dtype).reshape(B, nb, bs, Hkv, D)
                  .swapaxes(2, 3).reshape(B * nb, Hkv, bs, D))
        return pool.at[phys].set(
            blocks, mode="drop", unique_indices=True)

    return write(k_pool, k), write(v_pool, v)


def kv_write_by_block(T: int, bs: int, base):
    """Whether `paged_kv_write` puts a window of `T` positions from
    `base` into a pool of `bs`-position blocks a block at a time: the
    window is whole blocks (static) and every `base` is a multiple of
    `bs` (a value: a traced array in the program, the host's own
    number where the engine counts what a call will do)."""
    return T % bs == 0 and (base % bs == 0).all()


def segment_shift(pool, segment, segments: int):
    """What `paged_kv_write` and `paged_read` add to a table's entries
    to address segment `segment` (a traced scalar inside a loop) of a
    pool `[segments * NB, Hkv, bs, D]` that holds `segments` caches
    behind one table (`init_paged_cache`, a config's `cache_segments`):
    `segment * NB`. Block `segment * NB` is the segment's null block."""
    return jnp.asarray(segment, jnp.int32) * (pool.shape[0] // segments)


def paged_kv_write(cache, k, v, block_tables, base, shift=None):
    """`kv_write`: put the T new positions of each row, logical
    positions `base[b]..base[b]+T-1`, into the pooled cache
    `{'k','v': [NB, Hkv, bs, D]}` through `block_tables` [B, MB].
    Returns the updated (k pool, v pool). Anything the table does not
    cover (bucket padding, inactive lanes, a block a windowed layer has
    let go) lands in the null block 0 or nowhere; what block 0 holds is
    garbage by contract. `shift` (`segment_shift`) moves every entry,
    the null one too, into one segment of a pool that holds several.

    The grain follows the window. One that fills no whole block (`T`
    under `bs` or no multiple of it: the tick, a verify window, the
    smallest bucket) goes row by row. A window of whole blocks (a
    prompt's bucket, a chunk) goes block by block when every `base` is
    a multiple of `bs`, which the program asks at run time: a prompt
    starts at 0 or at a chunk's multiple of `bs`, but a prefix-cache
    hit that ends mid-block starts it inside one, and then the rows
    write it. Both leave every block but 0 the same, bit for bit."""
    T, bs = k.shape[1], cache["k"].shape[2]
    args = (cache["k"], cache["v"], k, v, block_tables, base)
    if shift is not None:
        args += (shift,)
    with jax.named_scope("kv_write"):
        by_block = kv_write_by_block(T, bs, base)
        if by_block is False:       # statically: no window of whole blocks
            return _kv_write_rows(*args)
        return jax.lax.cond(
            by_block, _kv_write_blocks, _kv_write_rows, *args)


def _table_slices(block_tables, fb, nb: int):
    """Columns `fb[b]..fb[b]+nb-1` of each row's table: the blocks a
    windowed layer's read takes of a chain."""
    return jax.vmap(
        lambda row, f: jax.lax.dynamic_slice_in_dim(row, f, nb)
    )(block_tables, fb)


def paged_gather_read(q, ck, cv, block_tables, base, first=None,
                      window: int = 0):
    """The gather read path: each row's chain gathered out of the pools
    into a contiguous view, then the masked grouped attention over it.
    q [B, T, H, D] at logical positions `base[b]..base[b]+T-1`.

    A `full` layer (`first` None) reads the whole table: keys 0..query.
    A windowed layer passes `first` [B], the first position its first
    query needs (`base - window + 1`, not below 0), and `window`: the
    read takes the `window_view_blocks` of the table that start at
    `first`'s block, not the whole chain, and a query at p sees keys
    `p - window < j <= p`. Rows beyond a row's frontier, and blocks the
    engine has let go (table entry 0), are masked off by position."""
    B, T = q.shape[0], q.shape[1]
    Hkv, bs = ck.shape[1], ck.shape[2]
    MB = block_tables.shape[1]
    rep = q.shape[2] // Hkv
    nb = MB if first is None else min(
        MB, window_view_blocks(window, T, bs))
    L = nb * bs
    # gather each row's chain, [B, nb, Hkv, bs, D]; rows beyond a row's
    # frontier are masked off exactly as in the slab layout
    with jax.named_scope("attention"):
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1)
        q_pos = base[:, None, None] + \
            jax.lax.broadcasted_iota(jnp.int32, (T, L), 0)[None]
        if first is None:
            mask = kv_pos[None] <= q_pos  # [B, T, L]
        else:
            fb = jnp.clip(first // bs, 0, MB - nb)              # [B]
            kv_pos = (fb * bs)[:, None, None] + kv_pos[None]
            mask = (kv_pos <= q_pos) & (kv_pos > q_pos - window)
    with jax.named_scope("kv_read"):
        if first is not None:
            block_tables = _table_slices(block_tables, fb, nb)
        # the gather and the layout copy; the cast to float32
        # that completes the read is scoped where it happens
        kview = _chain_view(ck, block_tables)
        vview = _chain_view(cv, block_tables)
    return _grouped_cache_attention(q, kview, vview, mask, rep)


def paged_tiled_read(q, ck, cv, block_tables, base, first=None,
                     window: int = 0):
    """The tiled read path of a prompt-length window: the chain is
    gathered as on the gather path (the same blocks of the same table,
    `paged_gather_read`'s), left in whole blocks `[B, nb, Hkv, bs, D]`
    in the cache's dtype, and the masked product over it is
    `ops.pallas.window_attention`: an online softmax a tile at a time,
    no score array in HBM, no key tile read that no query of the window
    sees. The view is padded to whole key tiles with copies of the
    table's last entry, which the kernel masks by position."""
    from hyperion_tpu.ops.pallas.window_attention import (
        view_tile,
        window_attention,
    )

    B, T, H, D = q.shape
    Hkv, bs = ck.shape[1], ck.shape[2]
    MB = block_tables.shape[1]
    nb = MB if first is None else min(
        MB, window_view_blocks(window, T, bs))
    per_tile = view_tile(T, H // Hkv, D, bs, nb, q.dtype, ck.dtype) // bs
    pad = -nb % per_tile
    with jax.named_scope("kv_read"):
        if first is None:
            fb = jnp.zeros_like(base)
        else:
            fb = jnp.clip(first // bs, 0, MB - nb)              # [B]
            block_tables = _table_slices(block_tables, fb, nb)
        if pad:
            block_tables = jnp.pad(
                block_tables, ((0, 0), (0, pad)), mode="edge")
        kview, vview = ck[block_tables], cv[block_tables]
    with jax.named_scope("attention"):
        return window_attention(
            q, kview, vview, base, fb * bs, window=window,
            keys=nb * bs if pad else 0)


def paged_read(impl: str, q, ck, cv, block_tables, base, window: int = 0,
               shift=None):
    """A paged layer's read by the path `impl` names ("pallas", "tiled"
    or "gather": `select_paged_attn_impl`'s answer for the call, or a
    config's explicit value): q [B, T, H, D] at positions
    `base[b]..base[b]+T-1` against the pools through one layer kind's
    table, a full layer's (`window` 0) or a windowed one's (a query at
    p sees keys `p - window < j <= p`). "pallas" walks the pools in
    place (the decode tick, a verify window); "tiled" gathers the chain
    and runs the tiled online softmax over it (`paged_tiled_read`: a
    prompt-length window on a TPU); "gather" gathers it and takes one
    softmax over the whole view (`paged_gather_read`: the oracle, every
    CPU run, the narrow prompt windows). `shift` (`segment_shift`)
    reads one segment of pools that hold several: every path is handed
    the shifted table and stays as it is (a null entry reads the
    segment's null block, masked by position as block 0 is)."""
    if shift is not None:
        block_tables = block_tables + shift
    if impl == "pallas":
        # read the pools in place: the kernel walks each row's live
        # blocks itself, so no contiguous copy is materialized. Its
        # read and its product are one kernel: all of it is `kv_read`,
        # the name the gather path's copies have
        from hyperion_tpu.ops.pallas.paged_attention import paged_attention

        with jax.named_scope("kv_read"):
            return paged_attention(q, ck, cv, block_tables, base,
                                   window=window)
    if impl not in ("gather", "tiled"):
        raise ValueError(
            f"unknown paged read {impl!r} (want 'gather', 'tiled' or "
            "'pallas'; a config's 'auto' resolves to one of them)")
    first = jnp.maximum(base - window + 1, 0) if window else None
    read = paged_tiled_read if impl == "tiled" else paged_gather_read
    return read(q, ck, cv, block_tables, base, first, window)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, rope_table, padding_mask, cache=None, cache_index=None,
                 block_tables=None):
        """Training path: cache=None → [B, T, d] out. Decode path:
        `cache` = {'k','v': [B, max_len, Hkv, D]} with `cache_index`
        tokens already filled → (out, updated cache); the T new
        positions are written at cache_index and attention runs over
        the filled prefix (dense left-to-right prompts only — no
        padding_mask in the cached path). `cache_index` may be a [B]
        vector of per-row depths (the serve engine's slots decode
        independent requests from one batched cache).

        Paged path: `block_tables` [B, MB] int32 switches `cache` to a
        pooled layout {'k','v': [num_blocks, Hkv, block_size, D]}
        (`init_paged_cache`): logical position p of row b lives at
        physical block `block_tables[b, p // bs]`, offset `p % bs`.
        `Hkv` sits ahead of `block_size` so one (block, head) tile is
        the array's last two dims — the only pool block the TPU kernel
        compiler accepts.
        Writes scatter through the table; reads either gather each
        row's blocks back into a contiguous [B, MB*bs] view for the
        same masked grouped attention (`paged_attn_impl="gather"`) or
        walk each row's live blocks in-kernel against the pools in
        place (`"pallas"`, ops.pallas.paged_attention: no contiguous
        copy); `"auto"`, the default, chooses per call from the window's
        width and the backend (`select_paged_attn_impl`), and on a TPU
        sends a prompt-length window to a third form: the gather, then
        a tiled online softmax over the view (`paged_tiled_read`).
        Out-of-range or unmapped positions
        route to physical block 0 (the serve engine's null block), so
        bucket padding can never corrupt a neighbour's blocks.

        With a [B] `cache_index` and T > 1 the call is a per-row
        verify window: row b's T tokens occupy positions
        cache_index[b]..cache_index[b]+T-1 under a per-row causal
        mask. The speculative tick leans on this — it writes the k+1
        window unconditionally and relies on rejected positions being
        masked invisible (length not advanced) and idempotently
        overwritten by the next window, so the KV cache never needs a
        rollback."""
        c = self.cfg
        dense = _dense_ctor(c)
        # The stretches of this call are `jax.named_scope`s, not
        # sub-modules: a scope names device time in a trace
        # (`attn/kv_read`, obs/xprof.py) and moves no parameter path,
        # where a module would rename `attn/q_proj` in every checkpoint.
        with jax.named_scope("qkv_proj"):
            q = dense(features=(c.n_heads, c.head_dim), name="q_proj")(x)
            k = dense(features=(c.n_kv_heads, c.head_dim), name="k_proj")(x)
            v = dense(features=(c.n_kv_heads, c.head_dim), name="v_proj")(x)
        offset = 0 if cache is None else cache_index
        with jax.named_scope("rope"):
            q = apply_rope(q, rope_table, offset)
            k = apply_rope(k, rope_table, offset)
        rep = c.n_heads // c.n_kv_heads

        def o_proj(out):
            with jax.named_scope("o_proj"):
                return dense(
                    features=c.d_model, axis=(-2, -1), name="o_proj")(out)

        if cache is not None and block_tables is not None:
            if isinstance(block_tables, dict):
                # the engine hands the tables by layer kind; every
                # layer here is `full`
                block_tables = block_tables["full"]
            B = x.shape[0]
            idx = jnp.asarray(cache_index, jnp.int32)
            base = idx if idx.ndim == 1 else jnp.full((B,), idx, jnp.int32)
            ck, cv = paged_kv_write(cache, k, v, block_tables, base)
            out = paged_read(c.paged_attn_for(x.shape[1]),
                             q, ck, cv, block_tables, base)
            return o_proj(out), {"k": ck, "v": cv}

        if cache is not None:
            T = x.shape[1]
            if getattr(cache_index, "ndim", 0) >= 1:
                # per-row offsets (serve engine: each slot at its own
                # depth): batched scatter of the T new positions at
                # row b's cache_index[b], and a per-row causal mask
                B = x.shape[0]
                rows = jnp.arange(B)[:, None]
                cols = cache_index[:, None] + jnp.arange(T)[None, :]
                with jax.named_scope("kv_write"):
                    ck = cache["k"].at[rows, cols].set(
                        k.astype(cache["k"].dtype))
                    cv = cache["v"].at[rows, cols].set(
                        v.astype(cache["v"].dtype))
                S = ck.shape[1]
                kv_pos = jax.lax.broadcasted_iota(jnp.int32, (T, S), 1)
                q_pos = cache_index[:, None, None] + \
                    jax.lax.broadcasted_iota(jnp.int32, (T, S), 0)[None]
                mask = kv_pos[None] <= q_pos  # [B, T, S]
            else:
                with jax.named_scope("kv_write"):
                    ck = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype),
                        (0, cache_index, 0, 0)
                    )
                    cv = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype),
                        (0, cache_index, 0, 0)
                    )
                # causal over global positions: query cache_index+i may
                # see cache rows 0..cache_index+i (the rest of the
                # buffer is zeros and masked off)
                S = ck.shape[1]
                kv_pos = jax.lax.broadcasted_iota(jnp.int32, (T, S), 1)
                q_pos = cache_index + jax.lax.broadcasted_iota(
                    jnp.int32, (T, S), 0
                )
                mask = kv_pos <= q_pos  # [T, S]
            new_cache = {"k": ck, "v": cv}
            out = _grouped_cache_attention(q, ck, cv, mask, rep)
            return o_proj(out), new_cache

        with jax.named_scope("attention"):
            if rep != 1:  # GQA: repeat kv heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            out = dot_product_attention(
                q, k, v, causal=True, padding_mask=padding_mask,
                impl=c.attention_impl
            )
        return o_proj(out)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        dense = _dense_ctor(c)
        gate = dense(features=c.ff_dim, name="gate_proj")(x)
        up = dense(features=c.ff_dim, name="up_proj")(x)
        return dense(features=c.d_model, name="down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, rope_table, padding_mask, cache=None, cache_index=None,
                 block_tables=None):
        c = self.cfg
        h = RMSNorm(c.norm_eps, c.compute_dtype, c.norm_impl, name="input_norm")(x)
        attn = LlamaAttention(c, name="attn")
        if cache is not None:
            a, cache = attn(h, rope_table, None, cache, cache_index,
                            block_tables)
        else:
            a = attn(h, rope_table, padding_mask)
        x = x + a
        h = RMSNorm(c.norm_eps, c.compute_dtype, c.norm_impl, name="post_attn_norm")(x)
        x = x + LlamaMLP(c, name="mlp")(h)
        return x if cache is None else (x, cache)


def init_cache(cfg: LlamaConfig, batch: int, max_len: int | None = None,
               dtype=None) -> list[dict]:
    """Per-layer KV cache buffers for incremental decoding."""
    max_len = max_len or cfg.max_len
    if max_len > cfg.max_len:
        # the rope table only has cfg.max_len rows; a longer cache would
        # silently clamp the dynamic slice and corrupt rotations
        raise ValueError(
            f"cache max_len {max_len} exceeds model max_len {cfg.max_len}"
        )
    dtype = dtype or cfg.compute_dtype
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(cfg.n_layers)
    ]


def init_paged_cache(cfg, num_blocks, block_size: int,
                     dtype=None) -> list[dict]:
    """Per-layer pooled KV cache for block-table decoding: physical
    block 0 is the null block (serve/blocks.py routes masked writes
    there), blocks 1..num_blocks-1 are allocatable. Logical positions
    addressed through a table must still stay under cfg.max_len — the
    rope table is the binding constraint, exactly as for `init_cache`.

    `num_blocks` is one number for every layer, or `{kind: number}` for
    a model whose `cfg.layer_kinds` names more than one kind: a layer's
    pool has its kind's size, and is addressed through its kind's
    table. A model that applies its layers `cfg.cache_steps` times a
    token (every other model: once) keeps that many caches a layer,
    and one that runs its layers as a `lax` loop over stacked weights
    keeps the caches of `cfg.pool_layers` layers in one array, because
    a loop's body cannot pick an array by a traced index. Its pools
    are `[cfg.cache_segments * n, ...]` (`cache_steps x pool_layers`
    segments): each segment (blocks `s * n ..`, the first of them the
    segment's null block) holds one layer's keys and values of one
    step, all behind the one table (`segment_shift`)."""
    dtype = dtype or cfg.compute_dtype
    segments = getattr(cfg, "cache_segments", 1)

    def pool(kind):
        n = num_blocks[kind] if isinstance(num_blocks, dict) else num_blocks
        shape = (segments * n, cfg.n_kv_heads, block_size, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # one pool a layer, or one a run of `pool_layers` layers
    return [pool(kind) for kind, _ in
            cfg.layer_kinds[::getattr(cfg, "pool_layers", 1)]]


def paged_cache_block_bytes(cfg, block_size: int, dtype=None,
                            kind: str | None = None) -> int:
    """HBM bytes one physical block costs across all layers (K and V) —
    the unit the serve cache-pressure gauges are denominated in. With
    `kind`, across the layers of that kind: a block of that kind's
    pool. A block id names `block_size` positions in every one of the
    model's `cache_steps` segments, so it costs that many times a
    layer's bytes."""
    dtype = jnp.dtype(dtype or cfg.compute_dtype)
    n_layers = cfg.n_layers if kind is None else sum(
        k == kind for k, _ in cfg.layer_kinds)
    n_layers *= getattr(cfg, "cache_steps", 1)
    return (2 * n_layers * block_size * cfg.n_kv_heads
            * cfg.head_dim * dtype.itemsize)


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, padding_mask=None, deterministic: bool = True,
                 cache=None, cache_index=None, block_tables=None):
        """input_ids int32 [B, T] → logits fp32 [B, T, vocab].

        Decode path: pass `cache` (from `init_cache`) and `cache_index`
        (tokens already filled) → (logits, updated cache). Used for both
        prefill (T = prompt length, cache_index 0) and single-token
        steps (T = 1). With `block_tables` [B, MB], `cache` is the
        pooled `init_paged_cache` layout and positions are addressed
        block-table-first (the serve engine's paged slots)."""
        c = self.cfg
        x = nn.Embed(
            c.vocab_size, c.d_model, dtype=c.compute_dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed_tokens",
        )(input_ids)
        rope = rope_frequencies(c.head_dim, c.max_len, c.rope_theta)
        block = LlamaBlock
        if cache is None and c.remat_policy != "none":
            from hyperion_tpu.precision.remat import REMAT_POLICIES

            block = nn.remat(LlamaBlock, policy=REMAT_POLICIES[c.remat_policy])
        new_cache = []
        for i in range(c.n_layers):
            blk = block(c, name=f"layer_{i}")
            if cache is None:
                x = blk(x, rope, padding_mask)
            else:
                x, layer_cache = blk(x, rope, None, cache[i], cache_index,
                                     block_tables)
                new_cache.append(layer_cache)
        x = RMSNorm(c.norm_eps, c.compute_dtype, c.norm_impl, name="final_norm")(x)
        # flax names the module `lm_head` already; the scope takes the
        # cast to float32 in with it
        with jax.named_scope("lm_head"):
            logits = _dense_ctor(c)(features=c.vocab_size, name="lm_head")(x)
            logits = logits.astype(jnp.float32)
        return logits if cache is None else (logits, new_cache)

    def init_params(self, rng: jax.Array, batch: int = 1, seq: int | None = None):
        ids = jnp.zeros((batch, seq or min(self.cfg.max_len, 128)), jnp.int32)
        return self.init(rng, ids)["params"]


# --- HF checkpoint interchange (local files only; zero-egress) ----------

_HF_LAYER_MAP = {
    "input_layernorm.weight": ("input_norm", "weight"),
    "post_attention_layernorm.weight": ("post_attn_norm", "weight"),
    "self_attn.q_proj.weight": ("attn", "q_proj", "kernel"),
    "self_attn.k_proj.weight": ("attn", "k_proj", "kernel"),
    "self_attn.v_proj.weight": ("attn", "v_proj", "kernel"),
    "self_attn.o_proj.weight": ("attn", "o_proj", "kernel"),
    "mlp.gate_proj.weight": ("mlp", "gate_proj", "kernel"),
    "mlp.up_proj.weight": ("mlp", "up_proj", "kernel"),
    "mlp.down_proj.weight": ("mlp", "down_proj", "kernel"),
}


def params_from_hf_state_dict(state: dict, cfg: LlamaConfig) -> dict:
    """Map an HF Llama state dict (torch tensors or ndarrays) onto our
    param tree. HF linear weights are [out, in] → transposed to flax
    [in, out]; q/k/v additionally reshape to (in, heads, head_dim) and
    o_proj to (heads, head_dim, out)."""

    def arr(v) -> np.ndarray:
        return np.asarray(v.float().numpy() if hasattr(v, "float") else v, np.float32)

    params: dict = {
        "embed_tokens": {"embedding": arr(state["model.embed_tokens.weight"])},
        "final_norm": {"weight": arr(state["model.norm.weight"])},
        "lm_head": {"kernel": arr(state["lm_head.weight"]).T},
    }
    for i in range(cfg.n_layers):
        layer: dict = {}
        for hf_name, path in _HF_LAYER_MAP.items():
            w = arr(state[f"model.layers.{i}.{hf_name}"])
            if path[-1] == "kernel":
                w = w.T  # [out, in] → [in, out]
                if path[1] in ("q_proj",):
                    w = w.reshape(cfg.d_model, cfg.n_heads, cfg.head_dim)
                elif path[1] in ("k_proj", "v_proj"):
                    w = w.reshape(cfg.d_model, cfg.n_kv_heads, cfg.head_dim)
                elif path[1] == "o_proj":
                    w = w.reshape(cfg.n_heads, cfg.head_dim, cfg.d_model)
            node = layer
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = w
        params[f"layer_{i}"] = layer
    return params


def load_hf_checkpoint(model_dir: str | Path, cfg: LlamaConfig) -> dict | None:
    """Load HF weights from a local directory (*.safetensors or
    pytorch_model*.bin shards). Returns None when absent — callers fall
    back to random init (SURVEY §7.3)."""
    model_dir = Path(model_dir)
    state: dict = {}
    sf = sorted(model_dir.glob("*.safetensors"))
    if sf:
        from safetensors.numpy import load_file

        for f in sf:
            state.update(load_file(f))
    else:
        bins = sorted(model_dir.glob("pytorch_model*.bin"))
        if not bins:
            return None
        import torch

        for f in bins:
            state.update(torch.load(f, map_location="cpu", weights_only=True))
    return params_from_hf_state_dict(state, cfg)
