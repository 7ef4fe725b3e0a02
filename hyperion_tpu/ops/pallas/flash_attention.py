"""Flash attention — the in-tree Pallas kernels for the framework's hot op.

Role in the stack (SURVEY §2.3): the reference's "tuned kernel" tier is
TorchInductor/Triton via `torch.compile(mode="max-autotune")`
(`compilation_optimization.py:96-103`); ours is this kernel pair,
selected with `attention_impl="pallas"` and benchmarked against the
plain-XLA attention by `compile_bench`.

Design (classic flash attention, TPU-shaped):

  * Forward: grid (batch, heads, q-blocks, kv-blocks) with the kv axis
    innermost and `dimension_semantics` marking it "arbitrary" — the kv
    sweep for one q tile revisits VMEM scratch (m, l, acc) across grid
    steps, so VMEM only ever holds one (block_q, block_kv) tile pair.
    K/V stream through as grid blocks; nothing loads a whole sequence,
    which is what makes the kernel a flash kernel beyond T~2k.
  * Matmuls run in the INPUT dtype with fp32 accumulation
    (`preferred_element_type=f32`) — bf16 inputs drive the MXU at full
    rate; casting operands to fp32 first would silently run 6-pass
    true-fp32 matmuls at ~1/6 peak (measured: 3.4 vs 15+ TFLOPS on
    v5e). Softmax statistics and accumulators stay fp32; p is cast
    back to the input dtype for the p@v / p^T@do dots (standard flash
    practice). Output cast to the input dtype at the end. The
    log-sum-exp per row is written as a second output — the residual
    the backward needs.
  * Causal programs skip kv tiles past the diagonal (`pl.when`) and
    mask the in-tile diagonal with broadcasted iotas — the standard
    ~2x FLOP saving.
  * Padding masks ([B, T], 1 = real) ride in as int32
    (SUBLANES, block_kv) tiles whose sublane rows are replicas.

  * Backward: the standard two-pass recomputation. A host-side
    `delta = sum(dO * O, -1)` (one fused XLA reduction), then two
    kernels that recompute the scaled logits tile-by-tile from q/k and
    the saved log-sum-exp (no [T, T] materialization anywhere):
      - dq kernel: grid (B, H, q-blocks, kv-blocks), dq accumulated in
        VMEM scratch over the kv sweep;
      - dk/dv kernel: grid (B, H, kv-blocks, q-blocks) — the transposed
        sweep — accumulating dk and dv in scratch over q tiles.
    p = exp(s - lse) reconstructs the softmax exactly (no per-tile max
    bookkeeping needed since lse is a true row constant).

Fully-masked rows (all-padding) produce garbage o/lse; their upstream
gradients are zero under any masked loss, and every backward term is
multiplied by dO or delta (both zero there), so gradients stay clean —
same caveat as every standard flash implementation.

On the CPU backend the kernels run in interpret mode so the full test
suite exercises them on the simulated CPU mesh; on a TPU they are
compiled; any other backend is refused (`ops/pallas/backend.py`).

TPU lowering note: Mosaic requires the last two dims of every physical
block to be (8, 128)-divisible or equal to the array dims
(`jax/_src/pallas/mosaic/lowering.py` `lower_jaxpr_to_module`). The
batch/head grid dims therefore use mapped (`None`) BlockSpec entries —
squeezed out of the kernel refs — and the per-row lse/delta tensors
carry a trailing LANES=128 broadcast dim at the kernel boundary
([B, H, T, 128], value replicated across lanes), because a [B, H, T]
row tensor admits no legal block: its second-to-last array dim is H,
and a (…, 1, block_q) block's 1 neither divides 8 nor equals H. The
lane replication (rather than a (1, block_q) lane-major layout) keeps
each stat sublane-aligned with its logits-tile row, so the kernels
slice [:, :1] with no relayout — the same layout
`jax.experimental.pallas.ops.tpu.flash_attention` uses for its l/m
stats. Only lane 0 is information: the VJP residual stores the compact
[B, H, T] slice, and `_flash_backward` re-broadcasts both lse and
delta transiently (so long-sequence configs don't hold 128x-replicated
fp32 stats across the fwd/bwd boundary). The padding mask rides as
int32 (not int8): a rank-1 int8 block needs 512-element tiling, int32
needs 128.
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
    LANES,      # lane-broadcast width for per-row stats (lse/delta)
    SUBLANES,   # sublane-broadcast height for the padding mask
    cost,
    interpret_on_backend,
)

# Defaults from the round-4 on-chip sweep (scripts/flash_block_probe.py,
# v5e, seq 4k/16k, D=64): 1024x1024 tiles reach 34 (fwd) / 41-44 (train)
# TFLOPS vs 3.8/6.5 at the old 128x128 — small tiles starve the MXU at
# D=64 — and beat XLA dense attention (~15) by >2.5x while keeping the
# flash memory profile. 2048-wide tiles fail to compile (VMEM: the fp32
# logits tile alone is block_q*block_kv*4 B).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_KV = 1024
# Performance-relevant revision of this kernel pair, stamped into every
# attention_bench CSV row so offline readers (compare_to_reference.py's
# auto-picks column) can tell a capture of THIS kernel from a stale one.
# Bump on any change that moves the measured xla/pallas crossover:
#   rev 2 — input-dtype MXU feeds (was fp32-cast 6-pass) + 1024x1024
#           tiles (was 128x128); the committed pre-fix capture carries
#           no rev column at all.
KERNEL_REV = 2


def default_blocks(head_dim: int) -> tuple[int, int]:
    """Head-dim-aware default tile sizes.

    The 1024x1024 sweep above ran at D=64 only; at D=128 (the Llama
    geometry) every (block, D) operand tile doubles and the backward
    holds four extra fp32 (block_q, block_kv) intermediates near the
    VMEM edge where 2048-wide tiles already fail at D=64. Until a
    D=128 on-chip sweep (scripts/flash_block_probe.py --head-dim 128)
    says otherwise, halve block_kv at D>=128 — the q-tile stays wide so
    the MXU contraction stays long."""
    if head_dim >= 128:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV // 2
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV


def _pick_block(T: int, want: int) -> int:
    """Resolve a block size against sequence length T.

    A request that exactly tiles T (min(want, T) divides T) is honored
    as-is — tests deliberately drive small blocks to exercise the
    multi-tile paths. Otherwise pick the largest 128-multiple divisor
    of T that is <= want (128-multiples keep the lse/delta rank-1
    blocks Mosaic-legal); a short sequence with no such divisor runs as
    one T-wide tile (with a warning above 1024, where the fp32 logits
    tile alone passes 4 MB and 2048x2048 is a known compile failure),
    and a long one raises rather than silently compiling a VMEM-busting
    single tile."""
    b = min(want, T)
    if T % b == 0:
        return b
    c = (b // 128) * 128
    while c >= 128:
        if T % c == 0:
            return c
        c -= 128
    if T <= 2048:
        if T > 1024:
            import warnings

            warnings.warn(
                f"flash_attention: seq length {T} has no 128-multiple "
                f"block divisor <= {want}; falling back to one {T}-wide "
                f"tile ({T * T * 4 / 2**20:.0f} MB fp32 logits per "
                "program, near the VMEM edge) — pad the sequence to a "
                "multiple of 128 for tiled execution",
                stacklevel=3,
            )
        return T
    raise ValueError(
        f"seq length {T} has no 128-multiple block divisor <= {want}; "
        f"pad the sequence or pass a block size that divides it"
    )


def _mask_arg(padding_mask):
    """[B, Tkv] mask → [B, SUBLANES, Tkv] int32: a [B, Tkv] array admits
    no legal TPU block (B sits in the second-to-last dim), so replicate
    rows across a sublane dim — the same trick jax's TPU flash kernel
    uses for kv segment ids."""
    B, Tkv = padding_mask.shape
    return jnp.broadcast_to(
        padding_mask.astype(jnp.int32)[:, None, :], (B, SUBLANES, Tkv)
    )


def _interpret() -> bool:
    return interpret_on_backend()


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


def _tile_mask(s, qi, ki, block_q, block_kv, causal, pad_ref):
    """Causal/padding mask for one (block_q, block_kv) logits tile."""
    mask = None
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        )
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        mask = kv_pos <= q_pos
    if pad_ref is not None:
        pad = pad_ref[0] > 0  # (block_kv,) — sublane rows are replicas
        pad = jnp.broadcast_to(pad[None, :], s.shape)
        mask = pad if mask is None else jnp.logical_and(mask, pad)
    if mask is None:
        return s
    return jnp.where(mask, s, NEG_INF)


# ---------------------------------------------------------------- forward


def _fwd_kernel(
    *refs, causal: bool, sm_scale: float,
    block_q: int, block_kv: int, n_kv: int,
    has_pad: bool, has_lse: bool,
):
    # positional refs: inputs (q, k, v[, pad]), outputs (o[, lse]),
    # scratch (m, l, acc). lse is only emitted when the VJP will
    # consume it — the inference path skips the [B, H, Tq, LANES]
    # HBM write entirely.
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    pad_ref = refs[i] if has_pad else None
    i += int(has_pad)
    o_ref = refs[i]
    i += 1
    lse_ref = refs[i] if has_lse else None
    i += int(has_lse)
    m_s, l_s, acc_s = refs[i:]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # causal: tiles fully above the diagonal contribute nothing
    relevant = (
        jnp.bool_(True) if not causal
        else ki * block_kv <= qi * block_q + block_q - 1
    )

    @pl.when(relevant)
    def _update():
        q = q_ref[...]   # (block_q, D), input dtype — MXU-rate matmul
        k = k_ref[...]   # (block_kv, D)
        v = v_ref[...]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_kv) fp32 accumulator
        s = _tile_mask(s, qi, ki, block_q, block_kv, causal, pad_ref)

        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_s[...] = m_new
        l_s[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    last_ki = (
        n_kv - 1 if not causal
        else jnp.minimum(n_kv - 1, (qi * block_q + block_q - 1) // block_kv)
    )

    @pl.when(ki == last_ki)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc_s[...] / l[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = jnp.broadcast_to(
                (m_s[...] + jnp.log(l))[:, None], lse_ref.shape
            )


def _scores(B, H, Tq, Tkv, causal) -> int:
    """Query-key pairs the attention needs: under the causal mask
    (key position <= query position) the lower triangle with its
    diagonal, whatever tiles a kernel visits."""
    per_head = Tq * Tkv
    if causal:
        n = min(Tq, Tkv)
        per_head = n * (n + 1) // 2 + (Tq - n) * Tkv
    return B * H * per_head


def _flash_forward(
    q, k, v, padding_mask, causal, block_q, block_kv, need_lse=True
):
    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    block_q = _pick_block(Tq, block_q)
    block_kv = _pick_block(Tkv, block_kv)
    # [B, T, H, D] → [B, H, T, D]: heads become a grid axis
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    n_q, n_kv = Tq // block_q, Tkv // block_kv
    n_scores = _scores(B, H, Tq, Tkv, causal)

    grid = (B, H, n_q, n_kv)
    # batch/head dims are mapped (None) so the physical blocks are the
    # Mosaic-legal (block_q, D) / (block_q,) shapes — see module note
    qspec = pl.BlockSpec(
        (None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0)
    )
    kvspec = pl.BlockSpec(
        (None, None, block_kv, D), lambda b, h, i, j: (b, h, j, 0)
    )
    in_specs = [qspec, kvspec, kvspec]
    args = [qT, kT, vT]
    if padding_mask is not None:
        in_specs.append(
            pl.BlockSpec(
                (None, SUBLANES, block_kv), lambda b, h, i, j: (b, 0, j)
            )
        )
        args.append(_mask_arg(padding_mask))

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        sm_scale=1.0 / (D ** 0.5),
        block_q=block_q,
        block_kv=block_kv,
        n_kv=n_kv,
        has_pad=padding_mask is not None,
        has_lse=need_lse,
    )

    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct(qT.shape, q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec(
            (None, None, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)
        ))
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, Tq, LANES), jnp.float32)
        )

    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        # QK^T and PV over the scores the mask keeps, one exp a score
        cost_estimate=cost(4 * n_scores * D, n_scores, *args, *out_shape),
    )(*args)
    o, lse = res if need_lse else (res[0], None)
    return o.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------- backward


def _dq_kernel(
    *refs, causal: bool, sm_scale: float,
    block_q: int, block_kv: int, n_kv: int,
):
    # inputs (q, k, v, do, lse, delta[, pad]), output dq, scratch dq_acc
    if len(refs) == 9:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, pad_ref, dq_ref, dq_s = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_s = refs
        pad_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    relevant = (
        jnp.bool_(True) if not causal
        else ki * block_kv <= qi * block_q + block_q - 1
    )

    @pl.when(relevant)
    def _update():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse = lse_ref[...][:, :1]    # (block_q, 1) — lane-broadcast stats
        delta = dl_ref[...][:, :1]   # (block_q, 1)

        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = _tile_mask(s, qi, ki, block_q, block_kv, causal, pad_ref)
        p = jnp.exp(s - lse)                               # exact softmax
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_s[...] = dq_s[...] + sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    last_ki = (
        n_kv - 1 if not causal
        else jnp.minimum(n_kv - 1, (qi * block_q + block_q - 1) // block_kv)
    )

    @pl.when(ki == last_ki)
    def _finalize():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, causal: bool, sm_scale: float,
    block_q: int, block_kv: int, n_q: int,
):
    # inputs (q, k, v, do, lse, delta[, pad]), outputs (dk, dv),
    # scratch (dk_acc, dv_acc); grid is (B, H, kv-blocks, q-blocks)
    if len(refs) == 11:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, pad_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        pad_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # causal: q tiles strictly above this kv tile's diagonal see nothing
    relevant = (
        jnp.bool_(True) if not causal
        else qi * block_q + block_q - 1 >= ki * block_kv
    )

    @pl.when(relevant)
    def _update():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse = lse_ref[...][:, :1]    # (block_q, 1)
        delta = dl_ref[...][:, :1]

        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_kv)
        s = _tile_mask(s, qi, ki, block_q, block_kv, causal, pad_ref)
        p = jnp.exp(s - lse)
        pt = p.astype(do.dtype)
        # dv += p^T do
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        # dk += scale * ds^T q
        dk_s[...] = dk_s[...] + sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, padding_mask, o, lse, g, causal, block_q, block_kv
):
    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    block_q = _pick_block(Tq, block_q)
    block_kv = _pick_block(Tkv, block_kv)
    n_q, n_kv = Tq // block_q, Tkv // block_kv

    # lse arrives compact [B, H, Tq] (the residual keeps only lane 0);
    # delta_i = sum_d dO_id * O_id is one fused XLA reduction. Both are
    # lane-broadcast to the kernels' [B, H, Tq, LANES] row-stat layout.
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        .transpose(0, 2, 1)[..., None],
        lse.shape,
    )

    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    gT = g.transpose(0, 2, 1, 3)

    sm_scale = 1.0 / (D ** 0.5)
    qspec = pl.BlockSpec(
        (None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0)
    )
    kvspec_dq = pl.BlockSpec(
        (None, None, block_kv, D), lambda b, h, i, j: (b, h, j, 0)
    )
    rowspec = pl.BlockSpec(
        (None, None, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)
    )

    mask_arg = None if padding_mask is None else _mask_arg(padding_mask)

    dq_in_specs = [qspec, kvspec_dq, kvspec_dq, qspec, rowspec, rowspec]
    dq_args = [qT, kT, vT, gT, lse, delta]
    if mask_arg is not None:
        dq_in_specs.append(
            pl.BlockSpec(
                (None, SUBLANES, block_kv), lambda b, h, i, j: (b, 0, j)
            )
        )
        dq_args.append(mask_arg)

    n_scores = _scores(B, H, Tq, Tkv, causal)
    dq_shape = jax.ShapeDtypeStruct(qT.shape, q.dtype)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_kv=block_kv, n_kv=n_kv,
        ),
        grid=(B, H, n_q, n_kv),
        in_specs=dq_in_specs,
        out_specs=qspec,
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        # the scores again, dP = dO V^T, dQ = dS K
        cost_estimate=cost(6 * n_scores * D, n_scores, *dq_args, dq_shape),
    )(*dq_args)

    # transposed sweep: kv tiles outer, q tiles inner
    qspec_t = pl.BlockSpec(
        (None, None, block_q, D), lambda b, h, j, i: (b, h, i, 0)
    )
    kvspec_t = pl.BlockSpec(
        (None, None, block_kv, D), lambda b, h, j, i: (b, h, j, 0)
    )
    rowspec_t = pl.BlockSpec(
        (None, None, block_q, LANES), lambda b, h, j, i: (b, h, i, 0)
    )

    dkv_in_specs = [qspec_t, kvspec_t, kvspec_t, qspec_t, rowspec_t, rowspec_t]
    dkv_args = [qT, kT, vT, gT, lse, delta]
    if mask_arg is not None:
        dkv_in_specs.append(
            pl.BlockSpec(
                (None, SUBLANES, block_kv), lambda b, h, j, i: (b, 0, j)
            )
        )
        dkv_args.append(mask_arg)

    dkv_shape = [
        jax.ShapeDtypeStruct(kT.shape, k.dtype),
        jax.ShapeDtypeStruct(vT.shape, v.dtype),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_kv=block_kv, n_q=n_q,
        ),
        grid=(B, H, n_kv, n_q),
        in_specs=dkv_in_specs,
        out_specs=[kvspec_t, kvspec_t],
        out_shape=dkv_shape,
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        # the scores again, dV = P^T dO, dP = dO V^T, dK = dS^T Q
        cost_estimate=cost(8 * n_scores * D, n_scores,
                           *dkv_args, *dkv_shape),
    )(*dkv_args)

    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash(causal, block_q, block_kv, q, k, v, padding_mask):
    out, _ = _flash_forward(
        q, k, v, padding_mask, causal, block_q, block_kv, need_lse=False
    )
    return out


_NARROWING_WARNED: set[tuple[str, str, str]] = set()


def _warn_if_narrowing(q_dtype, k_dtype, v_dtype) -> None:
    """Warn ONCE per dtype combination when reconciling k/v to q's dtype
    LOSES precision (k/v itemsize > q itemsize) — a bf16 query attending
    into an fp32 KV cache silently downcasts the cache on every call,
    which is a real numerics decision the caller should have made
    explicitly (cast q up, or store the cache in bf16)."""
    import warnings

    qd = jnp.dtype(q_dtype)
    for name, d in (("k", jnp.dtype(k_dtype)), ("v", jnp.dtype(v_dtype))):
        if d.itemsize > qd.itemsize:
            key = (str(qd), name, str(d))
            if key in _NARROWING_WARNED:
                continue
            _NARROWING_WARNED.add(key)
            warnings.warn(
                f"flash_attention: {name} is {d.name} but q is {qd.name}; "
                f"reconciling to q's dtype NARROWS {name} from "
                f"{d.itemsize * 8} to {qd.itemsize * 8} bits per element "
                "(e.g. a bf16 query against an fp32 KV cache). Cast q up, "
                "or store K/V in the compute dtype, if that precision "
                "matters. (warned once per dtype combination)",
                stacklevel=3,
            )


def flash_attention(
    q, k, v, *, causal: bool = False, padding_mask=None,
    block_q: int | None = None, block_kv: int | None = None,
):
    """Drop-in for `ops.attention.dot_product_attention` over
    [B, T, H, D] tensors. padding_mask: [B, Tkv], 1 = real token.

    block_q/block_kv default per head_dim (`default_blocks`); mixed
    q/k/v dtypes are reconciled to q's dtype (the kernels drive the MXU
    in one input dtype, no fp32 upcast — matching the XLA impl, which
    also computes in q's dtype; a narrowing reconciliation warns once —
    `_warn_if_narrowing`)."""
    if not (q.dtype == k.dtype == v.dtype):
        _warn_if_narrowing(q.dtype, k.dtype, v.dtype)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    dq, dkv = default_blocks(q.shape[-1])
    return _flash(
        causal, block_q or dq, block_kv or dkv, q, k, v, padding_mask
    )


def _fwd(causal, block_q, block_kv, q, k, v, padding_mask):
    out, lse = _flash_forward(q, k, v, padding_mask, causal, block_q, block_kv)
    # keep only lane 0 of the [B, H, Tq, LANES] stats as the residual
    return out, (q, k, v, padding_mask, out, lse[..., 0])


def _bwd(causal, block_q, block_kv, residuals, g):
    q, k, v, padding_mask, o, lse = residuals
    dq, dk, dv = _flash_backward(
        q, k, v, padding_mask, o, lse, g, causal, block_q, block_kv
    )
    # integer mask cotangent is float0 (None when no mask was passed)
    dmask = (
        None if padding_mask is None
        else np.zeros(padding_mask.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, dmask


_flash.defvjp(_fwd, _bwd)
