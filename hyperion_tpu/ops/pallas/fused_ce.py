"""Fused softmax cross-entropy — the loss-side Pallas kernel.

Role in the stack: third member of the `jit+pallas` tier (with flash
attention and the fused norms — the reference's max-autotune analogue,
`compilation_optimization.py:96-103`). For the GPT-2-vocab LMs the CE
over [N, 50257] logits is the largest non-matmul op in the train step;
XLA computes it as separate max / exp-sum / gather passes over HBM,
each touching the full logits array.

Kernel shape:

  * Forward: grid (row tiles, vocab tiles) with the vocab axis
    innermost and "arbitrary" — one streaming pass computes the online
    logsumexp (running max + rescaled sum, flash-attention style) AND
    picks out each row's target logit via an iota==target compare, so
    the [N, V] array is read exactly once. Outputs per-row loss
    (lse - target_logit) and the lse residual.
  * Backward: d_logits = (softmax - onehot(target)) * g, tile-by-tile
    from the saved lse — again one pass, nothing materialized beyond
    the output itself.

Rows/vocab are padded to tile multiples with NEG_INF columns (which
change neither lse nor gradients) and zero rows (sliced off). On the
CPU backend the kernels run in interpret mode, so the test suite
exercises them.

The per-row side operands (targets, loss, lse, g) and the three
running-statistic scratches are carried 2-D, replicated across the 128
lanes (`[N, LANES]`, the trick flash_attention.py uses for its mask).
As 1-D `(bn,)` blocks they lowered through `jax.export` and were then
refused by the chip's compiler: XLA tiles a 1-D s32/f32 array by 1024
and Mosaic by the block's 256, and no single `block_n` satisfies both
inside the VMEM limit. The replicas cost N*128*4 bytes of HBM per
operand, against N*V for the logits. `tests/test_tpu_compile.py`
compiles forward+backward at vocab 50257 and 32000 for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.attention import NEG_INF
from hyperion_tpu.ops.pallas.backend import (
    LANES,
    cost,
    interpret_on_backend,
)

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 2048


def _interpret() -> bool:
    return interpret_on_backend()


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
    )


# ---------------------------------------------------------------- forward


def _fwd_kernel(logits_ref, tgt_ref, loss_ref, lse_ref, m_s, l_s, t_s,
                *, block_v: int, n_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        t_s[...] = jnp.zeros_like(t_s)

    tile = logits_ref[...].astype(jnp.float32)       # [bn, bv]
    m_prev, l_prev = m_s[...], l_s[...]              # [bn, LANES]
    m_new = jnp.maximum(m_prev, tile.max(axis=-1, keepdims=True))
    l_s[...] = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(tile - m_new[:, :1]), axis=-1, keepdims=True
    )
    m_s[...] = m_new

    # target logit: each row's target falls in exactly one vocab tile
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    hit = col == tgt_ref[...][:, :1]
    t_s[...] = t_s[...] + jnp.sum(
        jnp.where(hit, tile, 0.0), axis=-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _finalize():
        lse = m_s[...] + jnp.log(jnp.maximum(l_s[...], 1e-37))
        lse_ref[...] = lse
        loss_ref[...] = lse - t_s[...]


# ---------------------------------------------------------------- backward


def _bwd_kernel(logits_ref, tgt_ref, lse_ref, g_ref, dlogits_ref,
                *, block_v: int):
    j = pl.program_id(1)
    tile = logits_ref[...].astype(jnp.float32)
    p = jnp.exp(tile - lse_ref[...][:, :1])
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    onehot = (col == tgt_ref[...][:, :1]).astype(jnp.float32)
    dlogits_ref[...] = (
        (p - onehot) * g_ref[...][:, :1]
    ).astype(dlogits_ref.dtype)


# ---------------------------------------------------------------- public


def _lanes(x):
    """[N] per-row operand -> [N, LANES], each row's value replicated
    across the lanes; the kernels read lane 0."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], LANES))


def _pad(logits, targets, block_n, block_v):
    N, V = logits.shape
    pn = (-N) % block_n
    pv = (-V) % block_v
    if pv:
        logits = jnp.pad(logits, ((0, 0), (0, pv)),
                         constant_values=NEG_INF)
    if pn:
        logits = jnp.pad(logits, ((0, pn), (0, 0)))
        targets = jnp.pad(targets, (0, pn))
    return logits, targets


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_softmax_xent(logits, targets, block_n=DEFAULT_BLOCK_N,
                       block_v=DEFAULT_BLOCK_V):
    """Per-row cross entropy: [N, V] float logits x [N] int targets →
    [N] fp32 losses (lse - target logit) — drop-in for
    `optax.softmax_cross_entropy_with_integer_labels`."""
    loss, _ = _fwd(logits, targets, block_n, block_v)
    return loss


def _run_forward(logits, targets, block_n, block_v):
    N = logits.shape[0]
    lp, tp = _pad(logits, targets, block_n, block_v)
    Np, Vp = lp.shape
    bn = min(block_n, Np)
    bv = min(block_v, Vp)
    n_v = Vp // bv
    row = pl.BlockSpec((bn, LANES), lambda i, j: (i, 0))
    args = (lp, _lanes(tp.astype(jnp.int32)))
    out_shape = [
        jax.ShapeDtypeStruct((Np, LANES), jnp.float32),
        jax.ShapeDtypeStruct((Np, LANES), jnp.float32),
    ]
    loss, lse_p = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=bv, n_v=n_v),
        grid=(Np // bn, n_v),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j)), row],
        out_specs=[row, row],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn, LANES), jnp.float32)] * 3,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        # per logit: the running max, the shift, the sum, the target's
        # pick; one exp a logit and one log a row
        cost_estimate=cost(4 * Np * Vp, Np * Vp + Np, *args, *out_shape),
    )(*args)
    # residuals keep the PADDED arrays so backward re-pads nothing —
    # padding the [N, V] logits twice would add a full extra HBM copy
    # of the step's largest tensor
    return loss[:N, 0], (lp, tp, lse_p[:, 0], (N, logits.shape[1]))


def _fwd(logits, targets, block_n, block_v):
    loss, residuals = _run_forward(logits, targets, block_n, block_v)
    return loss, residuals


def fused_softmax_xent_fwd_only(logits, targets, block_n=DEFAULT_BLOCK_N,
                                block_v=DEFAULT_BLOCK_V):
    """Forward without residual retention (eval paths)."""
    loss, _ = _run_forward(logits, targets, block_n, block_v)
    return loss


def _bwd(block_n, block_v, residuals, g):
    lp, tp, lse_p, (N, V) = residuals
    Np, Vp = lp.shape
    bn = min(block_n, Np)
    bv = min(block_v, Vp)
    g_p = jnp.pad(g.astype(jnp.float32), (0, Np - N))
    row = pl.BlockSpec((bn, LANES), lambda i, j: (i, 0))
    args = (lp, _lanes(tp.astype(jnp.int32)), _lanes(lse_p), _lanes(g_p))
    out_shape = jax.ShapeDtypeStruct((Np, Vp), lp.dtype)
    dlogits = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=bv),
        grid=(Np // bn, Vp // bv),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j)), row, row, row],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        # per logit: the shift, the target's one, the scale by g; one exp
        cost_estimate=cost(3 * Np * Vp, Np * Vp, *args, out_shape),
    )(*args)
    return dlogits[:N, :V], None


fused_softmax_xent.defvjp(_fwd, _bwd)
