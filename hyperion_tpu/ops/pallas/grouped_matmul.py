"""Grouped matrix product: rows sorted by group against a stack of
matrices, each touched matrix streamed from HBM once.

Role in the stack (ROADMAP S11): the serving expert step
(`ops/moe.py` `grouped_experts`) multiplies the (token, pick) rows,
sorted by expert, with the experts' stacked `gate`, `up` and `down`.
At a decode tick's shape (a few rows an expert, every expert touched)
the work is reading the matrices: 64 x 3.9 MB a product at
SmallThinker's widths. XLA's `ragged_dot` reads them at 51-55 % of the
HBM roofline there (PERF.md section 6, PR 35); this kernel moves each
touched group's matrix in one DMA of megabytes, the next in flight
while this one is multiplied.

The walk:

  * `lhs [M, K]` is cut into row tiles of `tm`. A VISIT is one (group,
    row tile) pair where the group has rows in the tile: groups in
    order, a group's tiles in order, so row tiles never go backwards.
    `group_visits` lists them (`group_ids`, `tile_ids`, how many there
    are) from `sizes`, once a layer: the three products of an expert
    layer share the same rows and so the same list. There are at most
    `M / tm + G - 1`.
  * Grid `(N / tn, visits)`, the visits innermost and their number
    read at run time: a group with no rows has no visit, and row tiles
    past `sum(sizes)` have none either, so neither their matrices nor
    their rows are ever fetched and their result rows are never
    written. (The expert step's rows for experts on other chips sort
    last and lie there: whatever the result holds for them must only
    not be read as a number, and the combine selects around it.)
  * The visit's operands ride the pipeline's double buffers: the
    group's `[K, tn]` slab of `rhs` (the whole matrix where it fits,
    `tn = N`: one contiguous copy), the row tile `[tm, K]`, the result
    tile `[tm, tn]`. Consecutive visits of one group name the same
    slab and consecutive visits of one row tile the same tiles, and
    the pipeline does not fetch a block again whose index stood still:
    every touched slab is read once, every row tile once an `n` pass.
  * A visit multiplies the whole row tile with the group's slab
    (float32 accumulation on the MXU) and stores only the rows that
    are the group's: the result tile stays in VMEM across the visits
    that share it and goes back to HBM when the walk leaves it.
    So `tm` is a trade: a visit's product costs `tm` rows whatever the
    group holds, and a group that straddles a tile edge is visited
    twice.

Numerics: operands as given (bf16 in serving), float32 accumulation,
the result in the operands' dtype: what `lax.ragged_dot` gives. Each
result row is one dot product chain over K in the MXU's order, so it
agrees with `ragged_dot` to float32 round-off, not bit for bit.

On the CPU backend the kernel runs through the Pallas interpreter
(tests); a program off a TPU never reaches it, because
`ops.moe.select_grouped_impl` sends no shape here there.
`tests/test_tpu_compile.py` compiles it for a described v5e at every
shape the selector sends.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperion_tpu.ops.pallas.backend import LANES, cost, interpret_on_backend

# What the two buffers of a group's slab may take, and what the call
# asks of the compiler: two whole [2560, 768] bf16 matrices (3.9 MB
# each) and the row tiles fit; two [3072, 3072] ones (18.9 MB each) do
# not and are cut in three along N, which reads no slower (PERF.md
# section 6, PR 35: column tiles of 1024, 1536 and 3072 within 1 %).
_RHS_BUDGET = 16 << 20
_VMEM_LIMIT = 32 << 20
# bf16 rows pack in pairs of sublanes: a row tile is a multiple of 16
_ROW_GRAIN = 16
# Row tiles in order of preference: the largest that divides the rows.
# On a v5e (same table) a visit of up to 128 rows costs the MXUs the
# same 2.6 us, under the 4.8 us of its 3.9 MB copy, so fewer, larger
# tiles only save visits: at [288, 64 groups] tiles of 48, 96 and 144
# read 0.336 ms a product, 32 0.338, 16 0.347; at [3072, 64] 128 reads
# 0.412, 64 0.426, 32 0.438 and 256 (a visit's product now 5 us) 0.419.
_ROW_TILES = (128, 96, 64, 48, 32, 16)


def _interpret() -> bool:
    return interpret_on_backend()


class GroupVisits(NamedTuple):
    """The walk of one `sizes` at one row tile (`group_visits`)."""
    offsets: jax.Array     # [G + 1] first row of each group; [G] = sum
    group_ids: jax.Array   # [M / tm + G - 1] the visit's group
    tile_ids: jax.Array    # [M / tm + G - 1] the visit's row tile
    count: jax.Array       # [1] how many visits there are
    tm: int                # the row tile they were listed for


def plan_tiles(m: int, k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tm, tn) for a call's static shape. `tn`: all of N where two
    buffers of a `[K, N]` slab fit `_RHS_BUDGET` (one contiguous copy a
    group), else the widest multiple of a lane tile that divides N and
    fits. `tm`: `row_tile`."""
    tn = n
    if 2 * k * n * itemsize > _RHS_BUDGET:
        fits = [t for t in range(LANES, n, LANES)
                if n % t == 0 and 2 * k * t * itemsize <= _RHS_BUDGET]
        if not fits:
            raise ValueError(
                f"no column tile of a [{k}, {n}] matrix fits "
                f"{_RHS_BUDGET} bytes of VMEM twice")
        tn = max(fits)
    return row_tile(m), tn


def row_tile(m: int) -> int:
    """The row tile for `m` rows: the largest of `_ROW_TILES` that
    divides `m`, else (no call of the serving path: the selector sends
    only multiples of the smallest) the smallest, with `m` padded up
    to it."""
    return next((t for t in _ROW_TILES if m % t == 0), _ROW_TILES[-1])


def group_visits(sizes: jax.Array, m: int, tm: int) -> GroupVisits:
    """The (group, row tile) pairs a product of `m` rows (padded up to
    a multiple of `tm`) in groups of `sizes` walks, in order. Every
    array is static in shape: `count` says how many entries are
    visits."""
    return GroupVisits(*_group_visits(sizes, -(-m // tm), tm), tm)


# A jit of its own, as the kernel's: a model lists the walk once a
# layer, and the layers share one trace of it.
@functools.partial(jax.jit, static_argnames=("tiles_m", "tm"))
def _group_visits(sizes, tiles_m, tm):
    sizes = sizes.astype(jnp.int32)
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # tiles a group has rows in: from the tile of its first row to the
    # tile of its last, none for a group with no rows
    first = starts // tm
    tiles = jnp.where(sizes == 0, 0, (ends + tm - 1) // tm - first)
    after = jnp.cumsum(tiles)                   # visits up to the group's
    visit = jnp.arange(tiles_m + G - 1, dtype=jnp.int32)
    # the visit's group: the first whose visits reach past it (entries
    # past the last visit name the last group and are never walked)
    group_ids = jnp.minimum(
        jnp.searchsorted(after, visit, side="right", method="compare_all"),
        G - 1).astype(jnp.int32)
    nth = visit - (after - tiles)[group_ids]
    tile_ids = jnp.minimum(first[group_ids] + nth, tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, group_ids, tile_ids, after[-1:]


def _visit_kernel(offsets_ref, groups_ref, tiles_ref, count_ref,
                  lhs_ref, rhs_ref, out_ref, *, tm):
    """One visit: the row tile times the group's slab, kept for the
    group's rows. lhs_ref [tm, K], rhs_ref [K, tn], out_ref [tm, tn]
    (resident across the visits of one row tile)."""
    del count_ref
    i = pl.program_id(1)
    g = groups_ref[i]
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    row = tiles_ref[i] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array, *,
                   visits: GroupVisits | None = None,
                   tiling: tuple[int, int] | None = None) -> jax.Array:
    """`lhs [M, K]` rows sorted by group, `rhs [G, K, N]`, `sizes [G]`
    int32 -> `[M, N]` in `lhs`'s dtype: rows `sum(sizes[:g]) ..
    sum(sizes[:g + 1])` times `rhs[g]`, float32 accumulation. Rows past
    `sum(sizes)` are not computed and hold no number.

    `visits`: `group_visits(sizes, M, tm)` made once for several
    products over the same rows (its `tm` is then the row tile);
    `tiling`: `(tm, tn)` in place of `plan_tiles`' (the probe and the
    tests walk other tilings with it)."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"want lhs [M, K] and rhs [G, K, N], got {lhs.shape} and "
            f"{rhs.shape}")
    if sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"sizes {sizes.shape} does not count rhs's {rhs.shape[0]} groups")
    M, K = lhs.shape
    N = rhs.shape[2]
    tm, tn = tiling or plan_tiles(M, K, N, rhs.dtype.itemsize)
    if visits is not None:
        tm = visits.tm
    if tm % _ROW_GRAIN or N % tn or (tn != N and tn % LANES):
        raise ValueError(
            f"tiling ({tm}, {tn}) does not cut [{M}, {K}] x [{K}, {N}]: "
            f"rows go in multiples of {_ROW_GRAIN}, columns in divisors "
            f"of N that are multiples of {LANES}")
    Mp = -(-M // tm) * tm
    if visits is None:
        visits = group_visits(sizes, M, tm)
    elif visits.group_ids.shape[0] != Mp // tm + rhs.shape[0] - 1:
        raise ValueError("visits were listed for another shape")
    if Mp != M:
        lhs = jnp.pad(lhs, ((0, Mp - M), (0, 0)))
    out = _grouped_matmul(lhs, rhs, *visits[:4], tm=tm, tn=tn,
                          interpret=_interpret())
    return out[:M]


# A jit of its own, as `paged_attention`'s: an expert layer calls the
# kernel three times and a model once a layer; calls of one shape share
# one trace of the body and one Mosaic module.
@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_matmul(lhs, rhs, offsets, group_ids, tile_ids, count, *,
                    tm, tn, interpret):
    M, K = lhs.shape
    G, _, N = rhs.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N // tn, count[0]),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, i, o, g, t, c: (t[i], 0)),
            pl.BlockSpec((None, K, tn), lambda j, i, o, g, t, c: (g[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, o, g, t, c: (t[i], j)),
    )
    out_shape = jax.ShapeDtypeStruct((M, N), lhs.dtype)
    return pl.pallas_call(
        functools.partial(_visit_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # a result tile is finished over consecutive visits: in order
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the static worst case, every group touched: rows and result
        # once, every group's matrix once. What a call reads follows
        # `sizes`, which no static estimate can see; the FLOPs are the
        # rows' own, not the row tiles a visit multiplies.
        cost_estimate=cost(2 * M * K * N, 0, lhs, rhs, out_shape,
                           offsets, group_ids, tile_ids, count),
    )(offsets, group_ids, tile_ids, count, lhs, rhs)
