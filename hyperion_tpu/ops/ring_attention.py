"""Ring attention — sequence/context parallelism over the `seq` mesh axis.

The reference has no long-context story at all (max seq 128, SURVEY
§5.7); this is the capability the TPU rebuild adds as first-class. The
idiomatic TPU form (SURVEY §5.7): shard the sequence axis across the
mesh and rotate K/V blocks around the ring with `ppermute` over ICI,
each device accumulating its queries' attention with an online softmax —
attention over sequences n_devices times longer than one chip could
hold, with communication overlapping compute around the ring.

Mechanics per ring step s (of n = |seq axis|):
    every device holds its local Q forever, and the K/V block that
    started s hops downstream; it computes Q·K^T against that block,
    folds it into running (m, l, acc) flash-attention stats, then
    ppermutes K/V one hop around the ring.
Causality uses *global* positions reconstructed from the ring indices,
so the result is bit-compatible (up to fp reassociation) with full
attention on the gathered sequence — asserted by tests on the CPU mesh.

Layout contract: q/k/v are [B, T, H, D] with T sharded over `seq`
(PartitionSpec(None, "seq")); everything else replicated or
batch-sharded as usual. Entry point `ring_attention` wraps the shard_map
so callers just pass the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from hyperion_tpu.utils import compat

from hyperion_tpu.ops.attention import NEG_INF
from hyperion_tpu.runtime.mesh import AxisName


def _local_ring_attention(
    q, k, v, pad, *, axis_name: str, causal: bool, scale: float
):
    """Runs inside shard_map. q/k/v: [B, T_local, H, D] (this device's
    shard); pad: [B, T_local] (1 = real) or None, rotating around the
    ring alongside the K/V block it masks. Returns [B, T_local, H, D]."""
    n = jax.lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape

    qf = q.astype(jnp.float32) * scale
    # fold heads into batch for the contraction: [B, H, Tl, D]
    qf = qf.transpose(0, 2, 1, 3)

    q_pos = my * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 0)

    def step(s, carry):
        k_blk, v_blk, pad_blk, m, l, acc = carry
        # the block currently held started on device (my - s) mod n
        src = jax.numpy.mod(my - s, n)
        kf = k_blk.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B,H,Tl,D]
        vf = v_blk.astype(jnp.float32).transpose(0, 2, 1, 3)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)

        if causal:
            kv_pos = src * Tl + lax.broadcasted_iota(jnp.int32, (Tl, Tl), 1)
            mask = kv_pos <= q_pos  # [Tl, Tl] in global positions
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        if pad_blk is not None:
            keep = (pad_blk > 0)[:, None, None, :]  # [B,1,1,Tl_kv]
            logits = jnp.where(keep, logits, NEG_INF)

        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vf)

        # rotate K/V (and their padding) one hop downstream (j → j+1)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if pad_blk is not None:
            pad_blk = lax.ppermute(pad_blk, axis_name, perm)
        return k_blk, v_blk, pad_blk, m_new, l_new, acc_new

    # fori_loop carries must carry the same varying-axes type as the
    # rotating K/V blocks (jax 0.9 shard_map tracks vma in loop types;
    # compat.vma_of/pvary no-op on jax versions without vma typing)
    vma = compat.vma_of(q)
    pvary = functools.partial(compat.pvary, axes=vma)
    m0 = pvary(jnp.full((B, H, Tl), NEG_INF, jnp.float32))
    l0 = pvary(jnp.zeros((B, H, Tl), jnp.float32))
    acc0 = pvary(jnp.zeros((B, H, Tl, D), jnp.float32))
    *_, m, l, acc = lax.fori_loop(0, n, step, (k, v, pad, m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, *,
    causal: bool = False, padding_mask: jax.Array | None = None,
    axis_name: str = AxisName.SEQ,
) -> jax.Array:
    """Attention over [B, T, H, D] with T sharded across `axis_name`.

    T must divide evenly over the axis. Batch stays sharded over the
    usual (data, fsdp) axes — the shard_map specs carry both.
    padding_mask: [B, T], 1 = real token; it rides the ring with K/V."""
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError(f"ring attention needs equal shapes, got {q.shape}/{k.shape}")
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(f"seq len {q.shape[1]} not divisible by {axis_name}={n}")
    scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(AxisName.BATCH, axis_name)  # [B@data,fsdp, T@seq, H, D]
    local = functools.partial(
        _local_ring_attention, axis_name=axis_name, causal=causal,
        scale=scale,
    )
    # optional padding rides as a fourth arg with a None spec when absent
    # (same pattern as ops.ulysses)
    pad_spec = P(AxisName.BATCH, axis_name) if padding_mask is not None else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, pad_spec),
        out_specs=spec,
    )
    return fn(q, k, v, padding_mask)


def seq_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [B, T, ...] activations in sequence-parallel regions."""
    return NamedSharding(mesh, P(AxisName.BATCH, AxisName.SEQ))
