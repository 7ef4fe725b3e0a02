"""Ulysses attention — all-to-all sequence parallelism over `seq`.

The second long-context strategy (SURVEY §2.2 lists Ulysses as absent in
the reference; the TPU rebuild carries both it and ring attention as
first-class). Where ring attention rotates K/V blocks around the mesh
with `ppermute` and never materializes the full sequence anywhere,
Ulysses re-shards: an `all_to_all` swaps the sharded axis from sequence
to heads, every device runs ordinary *full-sequence* attention on its
slice of heads, and a second `all_to_all` swaps back.

Trade-off (why both exist):
  * Ulysses does exactly 2 all-to-alls per attention call, and the local
    compute is a plain dense attention — so the in-tree Pallas flash
    kernel applies unmodified (`impl="pallas"`). But parallelism is
    capped by the head count, and each device holds the full sequence
    for its heads (memory O(T)).
  * Ring scales past the head count and keeps memory O(T/n), at the
    cost of n ppermute steps interleaved with compute.

Layout contract matches ring attention: q/k/v are [B, T, H, D] with T
sharded over `seq` (and batch over data/fsdp); H must be divisible by
the seq-axis size.
"""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from hyperion_tpu.ops.attention import dot_product_attention
from hyperion_tpu.runtime.mesh import AxisName


def _local_ulysses(q, k, v, pad, *, axis_name, causal, impl):
    """Inside shard_map: q/k/v [B, T/n, H, D] → attention via two
    all-to-alls. `pad` is [B, T/n] or None."""
    # seq-shard → head-shard: split heads (axis 2) across the axis,
    # concatenate received chunks along sequence (axis 1):
    # [B, T/n, H, D] → [B, T, H/n, D]
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    if pad is not None:
        # every device needs the whole padding mask: all_gather along seq
        pad = lax.all_gather(pad, axis_name, axis=1, tiled=True)  # [B, T]
    out = dot_product_attention(
        qh, kh, vh, causal=causal, padding_mask=pad, impl=impl,
    )
    # head-shard → seq-shard: the inverse all_to_all
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, *,
    causal: bool = False, padding_mask: jax.Array | None = None,
    axis_name: str = AxisName.SEQ, impl: str = "xla",
) -> jax.Array:
    """Attention over [B, T, H, D] with T sharded across `axis_name`,
    parallelized by re-sharding to heads (2 all-to-alls). `impl` selects
    the local attention kernel ("xla" | "pallas" — the flash kernel runs
    unmodified since each device sees the full sequence)."""
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError(
            f"ulysses attention needs equal shapes, got {q.shape}/{k.shape}"
        )
    n = mesh.shape[axis_name]
    B, T, H, D = q.shape
    if T % n:
        raise ValueError(f"seq len {T} not divisible by {axis_name}={n}")
    if H % n:
        raise ValueError(
            f"ulysses parallelism is capped by heads: H={H} not divisible "
            f"by {axis_name}={n} (use ring_attention past the head count)"
        )
    spec = P(AxisName.BATCH, axis_name)
    pad_spec = P(AxisName.BATCH, axis_name)
    args = (q, k, v)
    in_specs = [spec, spec, spec]
    if padding_mask is not None:
        args = args + (padding_mask,)
        in_specs.append(pad_spec)
    else:
        args = args + (None,)
        in_specs.append(None)

    fn = jax.shard_map(
        functools.partial(
            _local_ulysses, axis_name=axis_name, causal=causal, impl=impl
        ),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec,
        # pallas_call inside shard_map can't declare vma on its outputs
        # (jax 0.9); the wrapper's specs already pin the layout
        check_vma=False,
    )
    return fn(*args)
