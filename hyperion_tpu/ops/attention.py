"""Multi-head scaled-dot-product attention — the framework's hot op.

The reference leans on `torch.nn.TransformerEncoder` (its attention runs
in rocBLAS/MIOpen — `distributed_utils.py:75-88`) and on HF Llama's
attention for the 7B path (`distributed_utils.py:465-467`). Here the op
is in-tree with selectable implementations:

  impl="xla"     einsum formulation; XLA fuses softmax into the matmuls
                 and tiles them onto the MXU. The default tier.
  impl="pallas"  in-tree flash-attention Pallas kernel
                 (hyperion_tpu.ops.pallas.flash_attention) — the
                 Inductor/Triton "max-autotune" analogue.
  impl="auto"    geometry-aware choice between the two from the
                 committed on-chip crossover data (the jit+pallas
                 tier's default when no explicit impl is configured):
                 the flash kernel wins long-sequence training, dense
                 XLA wins short sequences — `select_attention_impl`.
  impl="ring"    sequence-parallel ring attention over the active
  impl="ulysses" mesh's seq axis (ops.ring_attention / ops.ulysses) —
                 a model config string turns on context parallelism.

Shapes follow the TPU-friendly [batch, seq, heads, head_dim] layout so
the seq axis shards directly for the sequence-parallel impls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -2.0 ** 30  # large-but-finite: keeps bf16 softmax NaN-free

# Crossover thresholds for impl="auto", from the committed v5e probe
# (results/benchmarks/attention/flash_block_probe.jsonl, round 4): the
# flash kernel's train-step TFLOPS pass XLA's dense attention between
# 2k and 4k (35-44 vs ~15.8 at 4k) while XLA leads ~7x at 1k forward;
# below the threshold the [T, T] logits tensor fits comfortably and
# XLA's single fused program beats the kernel's grid overhead.
PALLAS_MIN_SEQ = 4096
PALLAS_MAX_HEAD_DIM = 128  # larger head dims have no probe coverage


def select_attention_impl(
    seq_len: int, head_dim: int, mode: str = "train"
) -> str:
    """Resolve impl="auto" to "pallas" or "xla" from call geometry.

    The choice is static per traced shape (resolved at trace time, so
    jit sees ordinary branch-free code). `mode` is a hint for callers
    that know they are forward-only ("fwd"): the kernel's measured win
    is train-mode (fwd+bwd, where not materializing [T, T] pays twice);
    forward-only keeps XLA until the dense logits stop fitting."""
    if head_dim > PALLAS_MAX_HEAD_DIM or seq_len % 128:
        return "xla"
    if mode == "fwd":
        # fwd-only crossover sits higher: XLA fwd leads through 2k and
        # the kernel's fwd win only shows at 4k+ with big tiles; be
        # conservative and require 2x the train threshold
        return "pallas" if seq_len >= 2 * PALLAS_MIN_SEQ else "xla"
    return "pallas" if seq_len >= PALLAS_MIN_SEQ else "xla"


def window_view_blocks(window: int, T: int, block_size: int) -> int:
    """Blocks of a chain that hold every key T successive queries of a
    windowed layer can see: positions `p0 - window + 1 .. p0 + T - 1`
    for a first query at p0, wherever p0 falls inside its block. What
    the gather read copies of a windowed kind's table a slot, and the
    most the paged-attention kernel walks of it."""
    return -(-(window + T - 1) // block_size) + 1


def causal_mask(q_len: int, kv_len: int, dtype=jnp.bool_) -> jax.Array:
    """[q_len, kv_len] lower-triangular mask (True = attend), aligned to
    the *end* of the kv sequence (supports queries shorter than kv, as in
    decode steps)."""
    offset = kv_len - q_len
    q_pos = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0) + offset
    kv_pos = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
    return (kv_pos <= q_pos).astype(dtype)


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None,
) -> jax.Array:
    # q: [B, Tq, H, D]; k/v: [B, Tkv, H, D]; mask: broadcastable to
    # [B, H, Tq, Tkv], True = attend.
    depth = q.shape[-1]
    # scale q in the compute dtype (rounding here is below the bf16
    # matmul's own quantization noise); the MXU accumulates in fp32
    scale = jnp.asarray(1.0 / jnp.sqrt(depth), q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.asarray(NEG_INF, logits.dtype))
    # softmax in fp32 regardless of compute dtype (bf16 softmax loses
    # precision exactly where attention needs it)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    padding_mask: jax.Array | None = None,
    impl: str = "xla",
) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors.

    padding_mask: [B, Tkv] with 1 = real token, 0 = pad (the reference's
    `attention_mask` column — dataset_preparation.ipynb cell 3).
    """
    if q.ndim != 4 or k.shape != v.shape or q.shape[-1] != k.shape[-1]:
        raise ValueError(f"bad attention shapes q={q.shape} k={k.shape} v={v.shape}")
    if impl in ("auto", "auto:fwd"):
        impl = select_attention_impl(
            q.shape[1], q.shape[-1],
            mode="fwd" if impl.endswith(":fwd") else "train",
        )
    if impl == "pallas":
        try:
            from hyperion_tpu.ops.pallas.flash_attention import flash_attention
        except ModuleNotFoundError as e:
            raise NotImplementedError(
                "the pallas attention tier is not built yet; use impl='xla'"
            ) from e
        return flash_attention(q, k, v, causal=causal, padding_mask=padding_mask)
    # "ulysses:pallas" etc. — sequence-parallel strategy plus the local
    # kernel it should run per shard (ulysses' full-sequence local
    # attention can use the flash kernel; ring has its own inner loop)
    strategy, _, local_impl = impl.partition(":")
    if strategy in ("ring", "ulysses"):
        from hyperion_tpu.runtime.mesh import active_mesh

        mesh = active_mesh()
        if mesh is None:
            raise ValueError(
                f"impl={impl!r} needs an active mesh — trainers register "
                "theirs via runtime.mesh.set_active_mesh before tracing"
            )
        if strategy == "ring":
            from hyperion_tpu.ops.ring_attention import ring_attention

            return ring_attention(
                q, k, v, mesh, causal=causal, padding_mask=padding_mask
            )
        from hyperion_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, mesh, causal=causal, padding_mask=padding_mask,
            impl=local_impl or "xla",
        )
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}")

    mask = None
    if causal:
        mask = causal_mask(q.shape[1], k.shape[1])[None, None]
    if padding_mask is not None:
        pad = padding_mask[:, None, None, :].astype(jnp.bool_)
        mask = pad if mask is None else jnp.logical_and(mask, pad)
    return _xla_attention(q, k, v, mask)


@functools.partial(jax.jit, static_argnames=("causal",))
def reference_attention(q, k, v, causal: bool = False):
    """Tiny jitted convenience wrapper used by kernel correctness tests."""
    return dot_product_attention(q, k, v, causal=causal)
