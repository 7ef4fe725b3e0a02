"""The gated delta rule's one-position update over the state rows a
decode tick advances, each read once and written once, in place.

Role in the stack (ROADMAP R6): a linear-attention layer of
`models/solar_open2.py` keeps one float32 state row a slot, `S [H, K,
V]` (4.19 MB at 64 heads of 128). The decode tick's update in
`jax.numpy` (`delta_rule_step`, then a select that keeps the rows the
tick does not advance) reads every slot's row twice and writes it once,
live or not. This kernel reads the advanced rows once and writes them
once where they lie, and never touches the others.

The walk, `grouped_matmul`'s:

  * A VISIT is one advanced row. `row_visits` lists them in ascending
    order from the tick's `advance` mask, and how many there are; the
    entries past the count are never walked.
  * Grid `(H / hb, visits)`, the visits innermost and their number
    read at run time: a row that does not advance has no visit, so its
    state, its inputs and its output are never fetched or written.
    With no row advanced the grid is empty and the kernel runs no step.
  * The state goes in and comes out through one buffer
    (`input_output_aliases`; the tick donates its cache, so the update
    is in place). A visit's `[hb, K, V]` block of S, and its rows of q,
    k, v, g and beta, ride the pipeline's double buffers: the next
    visit's block is in flight while this one is updated.
  * The pipeline writes an output block back to HBM only after a step
    that names it, and every step writes the whole of the block it
    names. The output's index map names only listed rows, each once a
    head block. So a block of S the kernel did not write is never
    written back: the rows not advanced keep their bytes.

Numerics: `delta_rule_step`'s equations in float32, elementwise
products and sums (no MXU pass): with `Sd = exp(g) S`, `u = beta (v -
k^T Sd)`, `o = q^T Sd + u (q.k)` and `S' = Sd + k u^T`. They agree
with it to float32 round-off (on a v5e at the serving cell's shape, bit
for bit: PERF.md section 6). The output row of a row not
visited holds no number: the caller selects around it.

On the CPU backend the kernel runs through the Pallas interpreter
(tests); a program off a TPU never reaches it, because
`models.solar_open2.select_state_update_impl` sends no shape here there.
`tests/test_tpu_compile.py` compiles it for a described v5e at the
serving cell's tick shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.pallas.backend import (
    SUBLANES,
    cost,
    interpret_on_backend,
)

# A visit's block of S, and what the call asks of the compiler: the
# block in and out, twice each for the pipeline. On a v5e (PERF.md
# section 6) blocks of 16 to 64 heads of [128, 128] read within
# 1.5 % of each other, 8 heads 10-12 % slower: 32 heads, 2 MiB.
_BLOCK_BUDGET = 2 << 20
_VMEM_LIMIT = 32 << 20


def _interpret() -> bool:
    return interpret_on_backend()


def head_block(h: int, k: int, v: int) -> int:
    """The heads a visit updates: among the divisors of `h` that are a
    multiple of a sublane tile, or all of `h` (a block's rows of q, k
    and g are `[hb, K]`), the most whose `[hb, K, V]` float32 block
    fits `_BLOCK_BUDGET`, else the fewest."""
    blocks = [hb for hb in range(1, h + 1)
              if h % hb == 0 and (hb % SUBLANES == 0 or hb == h)]
    fits = [hb for hb in blocks if hb * k * v * 4 <= _BLOCK_BUDGET]
    return max(fits) if fits else min(blocks)


def row_visits(advance: jax.Array) -> tuple[jax.Array, jax.Array]:
    """`advance [B]` bool -> (rows [B] int32, count [1] int32): the
    advanced rows in ascending order, then the others (never walked),
    and how many advanced."""
    order = jnp.argsort(jnp.logical_not(advance), stable=True)
    return order.astype(jnp.int32), \
        jnp.sum(advance.astype(jnp.int32)).reshape(1)


def _visit_kernel(rows_ref, count_ref, q_ref, k_ref, v_ref, g_ref,
                  beta_ref, s_ref, o_ref, s_out_ref):
    """One visit: one row's `hb` heads. q, k, g [hb, K], v [hb, V],
    beta [hb, 1], S in and out [hb, K, V], o [hb, V]. A group of heads
    at a time (a sublane tile of them, or the whole block where no
    tile divides it): its q, k and decays turned once into columns (K
    on the sublanes, as S has it), then each head's `[K, V]` decayed,
    read and updated."""
    del rows_ref, count_ref
    hb = s_ref.shape[0]
    n = SUBLANES if hb % SUBLANES == 0 else hb

    def group(j, carry):
        heads = pl.ds(pl.multiple_of(j * n, n), n)
        q, k = q_ref[heads, :], k_ref[heads, :]
        v, beta = v_ref[heads, :], beta_ref[heads, :]
        qk = jnp.sum(q * k, -1, keepdims=True)              # [n, 1]
        decay, qc, kc = jnp.exp(g_ref[heads, :]).T, q.T, k.T  # [K, n]
        o = []
        for i in range(n):
            h = j * n + i
            Sd = decay[:, i:i + 1] * s_ref[h]
            u = beta[i:i + 1] * (
                v[i:i + 1] - jnp.sum(kc[:, i:i + 1] * Sd, 0, keepdims=True))
            o.append(jnp.sum(qc[:, i:i + 1] * Sd, 0, keepdims=True)
                     + u * qk[i:i + 1])
            s_out_ref[h] = Sd + kc[:, i:i + 1] * u
        o_ref[heads, :] = jnp.concatenate(o, 0)
        return carry

    lax.fori_loop(0, hb // n, group, 0)


def delta_rule_step_rows(q, k, v, g, beta, S, advance):
    """`delta_rule_step` on the rows `advance` names, in place. q, k, g
    [B, H, K] (q scaled, g log-decays), v [B, H, V], beta [B, H], S
    [B, H, K, V] float32, advance [B] bool -> (o [B, H, V], S'). A row
    not advanced keeps its state bit for bit; its `o` row holds no
    number."""
    B, H, K = k.shape
    V = v.shape[-1]
    if S.shape != (B, H, K, V) or S.dtype != jnp.float32:
        raise ValueError(
            f"want S [{B}, {H}, {K}, {V}] float32, got {S.shape} {S.dtype}")
    hb = head_block(H, K, V)
    rows, count = row_visits(advance)
    f32 = jnp.float32
    return _state_update(rows, count, q.astype(f32), k.astype(f32),
                         v.astype(f32), g.astype(f32),
                         beta.astype(f32)[..., None], S, hb=hb,
                         interpret=_interpret())


# A jit of its own, as `grouped_matmul`'s: the tick calls the kernel
# once a recurrent layer, and the layers share one trace of the body
# and one Mosaic module.
@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _state_update(rows, count, q, k, v, g, beta, S, *, hb, interpret):
    B, H, K, V = S.shape

    def at(width):
        return pl.BlockSpec((None, hb, width),
                            lambda j, i, r, c: (r[i], j, 0))

    state = pl.BlockSpec((None, hb, K, V),
                         lambda j, i, r, c: (r[i], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // hb, count[0]),
        in_specs=[at(K), at(K), at(V), at(K), at(1), state],
        out_specs=[at(V), state],
    )
    out_shape = [jax.ShapeDtypeStruct((B, H, V), jnp.float32),
                 jax.ShapeDtypeStruct(S.shape, jnp.float32)]
    return pl.pallas_call(
        _visit_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # S (operand 7, after the two scalar lists) is the second result
        input_output_aliases={7: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the static worst case, every row advanced: per element of S
        # the decay, two multiply-adds for the sums and one for the
        # update; per (row, head) `q.k` and `u`'s and `o`'s vectors;
        # every input once, S read once and written once
        cost_estimate=cost(
            B * H * (7 * K * V + 2 * K + 4 * V), B * H * K,
            rows, count, q, k, v, g, beta, S, *out_shape),
    )(rows, count, q, k, v, g, beta, S)
