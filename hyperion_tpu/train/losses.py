"""Loss functions shared by every trainer.

Reference semantics being matched:
  * LM: next-token shift inside the step (`x, y = ids[:, :-1], ids[:, 1:]`,
    `distributed_utils.py:172`) with CrossEntropyLoss(ignore_index=pad)
    (`:162`) — pad positions contribute nothing to loss or denominator.
  * CIFAR: plain CE over 10 classes plus running correct/total counts for
    accuracy (`distributed_utils.py:248-252`).

All reductions are computed in fp32 regardless of compute dtype; under
`jit` over a sharded batch the means/sums below are *global* — XLA inserts
the cross-device psum that DDP's explicit `all_reduce` performed
(`distributed_utils.py:183-185, 254-257`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def next_token_loss(
    logits: jax.Array,      # [B, T, V] fp32
    input_ids: jax.Array,   # [B, T] int32
    attention_mask: jax.Array | None = None,  # [B, T] 1=real
    impl: str = "xla",      # xla | pallas (ops.pallas.fused_ce)
) -> jax.Array:
    """Causal-LM loss with the reference's shift-and-ignore-pad semantics.

    The model sees positions 0..T-1 and predicts 1..T; position t's logits
    are scored against token t+1. A target is counted only when it is a
    real (non-pad) token. impl="pallas" streams the vocab axis through
    the fused logsumexp+gather kernel (one HBM pass over the logits).
    The whole of it runs under the scope `loss` (obs/xprof.py).
    """
    with jax.named_scope("loss"):
        return _next_token_loss(logits, input_ids, attention_mask, impl)


def _next_token_loss(logits, input_ids, attention_mask, impl):
    targets = input_ids[:, 1:]
    pred = logits[:, :-1].astype(jnp.float32)
    if impl == "pallas":
        from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent

        B, Tm1, V = pred.shape
        per_tok = fused_softmax_xent(
            pred.reshape(B * Tm1, V), targets.reshape(B * Tm1)
        ).reshape(B, Tm1)
    else:
        per_tok = optax.softmax_cross_entropy_with_integer_labels(pred, targets)
    if attention_mask is None:
        return per_tok.mean()
    w = attention_mask[:, 1:].astype(jnp.float32)
    return (per_tok * w).sum() / jnp.maximum(w.sum(), 1.0)


def classification_loss(
    logits: jax.Array,   # [B, C] fp32
    labels: jax.Array,   # [B] int32
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """CE loss + the accuracy counts the CIFAR trainer aggregates
    (correct/total as fp32 sums, so they psum across the mesh)."""
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    ).mean()
    correct = (logits.argmax(-1) == labels).sum().astype(jnp.float32)
    total = jnp.asarray(labels.shape[0], jnp.float32)
    return loss, {"correct": correct, "total": total}
