"""Fused residual-add + LayerNorm / RMSNorm Pallas kernels.

The second of the two "tuned tier" kernels (SURVEY §7.1: "fused
attention, fused LN/residual"). XLA usually fuses LN chains well on its
own — these kernels exist to (a) guarantee the fusion (one HBM
round-trip for `residual + x` → normalize → scale/shift) and (b) be the
measurable Pallas-vs-XLA data point `compile_bench` reports alongside
attention. `fused_rmsnorm` is the Llama-family variant (no mean
subtraction, no bias — matches `models.llama.RMSNorm`).

Statistics are computed in fp32 regardless of input dtype (bf16 mean/var
is exactly where LN goes wrong); the normalized output is cast back.

Backward: custom_vjp recomputing via the plain-jnp formulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from hyperion_tpu.ops.pallas.backend import cost, interpret_on_backend

DEFAULT_BLOCK_ROWS = 256


def _interpret() -> bool:
    return interpret_on_backend()


def _kernel(x_ref, res_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    if res_ref is not None:
        x = x + res_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _kernel_no_res(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    _kernel(x_ref, None, w_ref, b_ref, o_ref, eps=eps)


def _row_blocked_call(kernel, x, extra_row_args, vec_args, block_rows,
                      flops_per_element):
    """Shared scaffolding for row-wise norm kernels: flatten to
    (rows, d), tile rows into blocks, broadcast the [d]-shaped vectors
    to every block, run one fused pass. `flops_per_element` is the
    kernel's arithmetic on one element of x, for its cost estimate."""
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    rows = x2.shape[0]
    block = min(block_rows, rows)
    if rows % block:
        block = rows  # odd row counts: single block (still one fused pass)

    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((d,), lambda i: (0,))
    args = [x2] + [a.reshape(-1, d) for a in extra_row_args] + list(vec_args)
    in_specs = (
        [row_spec] * (1 + len(extra_row_args)) + [vec_spec] * len(vec_args)
    )
    out_shape = jax.ShapeDtypeStruct(x2.shape, x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=out_shape,
        interpret=_interpret(),
        # one rsqrt a row; every operand and the result move once
        cost_estimate=cost(flops_per_element * rows * d, rows,
                           *args, out_shape),
    )(*args)
    return out.reshape(orig_shape)


def _forward(x, residual, weight, bias, eps, block_rows):
    # mean (1), centred square and its mean (3), normalise (2), scale
    # and shift (2); the residual's add is one more
    if residual is not None:
        return _row_blocked_call(
            functools.partial(_kernel, eps=eps),
            x, [residual], [weight, bias], block_rows, 9,
        )
    return _row_blocked_call(
        functools.partial(_kernel_no_res, eps=eps),
        x, [], [weight, bias], block_rows, 8,
    )


def _reference(x, residual, weight, bias, eps):
    h = x.astype(jnp.float32)
    if residual is not None:
        h = h + residual.astype(jnp.float32)
    mean = jnp.mean(h, -1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), -1, keepdims=True)
    y = (h - mean) * jax.lax.rsqrt(var + eps) * weight + bias
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused(eps, block_rows, x, residual, weight, bias):
    return _forward(x, residual, weight, bias, eps, block_rows)


def fused_layernorm(
    x, weight, bias, *, residual=None, eps: float = 1e-5,
    block_rows: int = DEFAULT_BLOCK_ROWS,
):
    """`LayerNorm(x + residual) * weight + bias` in one HBM pass.
    x: [..., d]; weight/bias: [d]; residual: same shape as x or None."""
    return _fused(eps, block_rows, x, residual, weight, bias)


def _fwd(eps, block_rows, x, residual, weight, bias):
    out = _forward(x, residual, weight, bias, eps, block_rows)
    return out, (x, residual, weight, bias)


def _bwd(eps, block_rows, res, g):
    x, residual, weight, bias = res
    if residual is None:
        _, vjp = jax.vjp(lambda x, w, b: _reference(x, None, w, b, eps),
                         x, weight, bias)
        dx, dw, db = vjp(g)
        return dx, None, dw, db
    _, vjp = jax.vjp(lambda x, r, w, b: _reference(x, r, w, b, eps),
                     x, residual, weight, bias)
    return vjp(g)


_fused.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------- RMSNorm


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _rms_forward(x, weight, eps, block_rows):
    # square and its mean (2), normalise (1), scale (1)
    return _row_blocked_call(
        functools.partial(_rms_kernel, eps=eps), x, [], [weight], block_rows,
        4,
    )


def _rms_reference(x, weight, eps):
    h = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(h), -1, keepdims=True)
    return (h * jax.lax.rsqrt(ms + eps) * weight.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_rms(eps, block_rows, x, weight):
    return _rms_forward(x, weight, eps, block_rows)


def fused_rmsnorm(
    x, weight, *, eps: float = 1e-5, block_rows: int = DEFAULT_BLOCK_ROWS
):
    """`x * rsqrt(mean(x^2) + eps) * weight` in one HBM pass.
    x: [..., d]; weight: [d]."""
    return _fused_rms(eps, block_rows, x, weight)


def _rms_fwd(eps, block_rows, x, weight):
    return _rms_forward(x, weight, eps, block_rows), (x, weight)


def _rms_bwd(eps, block_rows, res, g):
    x, weight = res
    _, vjp = jax.vjp(lambda x, w: _rms_reference(x, w, eps), x, weight)
    return vjp(g)


_fused_rms.defvjp(_rms_fwd, _rms_bwd)
