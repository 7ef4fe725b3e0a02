"""The jit-compiled train step — the framework's hot loop.

Reference hot loop (per step): autocast forward + loss, scaled backward
(DDP all-reduces grads inside backward), optimizer step, scaler update
(`distributed_utils.py:170-180`). Here the whole step is ONE compiled XLA
program: forward, backward, any collectives the sharding implies
(grad psum for DP, all-gather/reduce-scatter for FSDP, row/col-parallel
psums for TP), clip, and the optimizer update — fused and scheduled by
the compiler, with buffers donated so params/opt-state update in place.

Gradient accumulation is a `lax.scan` over microbatches (the reference's
`gradient_accumulation_steps` config knob that its code never implements
— default_config.json:9 — implemented for real here).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from hyperion_tpu.train.state import StateSharding, TrainState

# loss_fn(params, batch_stats, batch, rngs) ->
#   (loss, (metrics dict, new_batch_stats))
LossFn = Callable[[Any, Any, dict, dict | None], tuple[jax.Array, tuple]]


def _split_microbatches(batch: dict, n: int) -> dict:
    def split(x):
        b = x.shape[0]
        if b % n:
            raise ValueError(f"batch {b} not divisible by grad_accum {n}")
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(split, batch)


def make_train_step(
    loss_fn: LossFn,
    optimizer: optax.GradientTransformation,
    sharding: StateSharding,
    grad_accum: int = 1,
    donate: bool = True,
    dropout: bool = False,
    sum_metrics: tuple[str, ...] = ("correct", "total"),
):
    """Compile the train step against a fixed state layout.

    Signature of the returned fn: `(state, batch, rng) -> (state, metrics)`.
    `rng` is folded with the step counter so dropout differs per step
    without threading a key chain through the host loop.

    `sum_metrics` declares which metric keys are counts (summed across
    microbatches under grad accumulation); everything else is averaged.
    Callers introducing new count-style metrics must list them here.
    """
    replicated = NamedSharding(sharding.mesh, P())

    def grads_and_metrics(params, batch_stats, batch, rngs):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        if grad_accum == 1:
            (_, (metrics, new_bs)), grads = grad_fn(params, batch_stats, batch, rngs)
            return grads, metrics, new_bs

        micro = _split_microbatches(batch, grad_accum)

        def body(carry, idx_and_mb):
            i, mb = idx_and_mb
            grads_acc, bs = carry
            # independent dropout mask per microbatch — otherwise rows at
            # the same position share a mask and accumulation diverges
            # from single-large-batch semantics
            mb_rngs = (
                {k: jax.random.fold_in(r, i) for k, r in rngs.items()}
                if rngs else None
            )
            (_, (metrics, new_bs)), grads = grad_fn(params, bs, mb, mb_rngs)
            with jax.named_scope("grad_accum"):
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                )
            return (grads_acc, new_bs), metrics

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, new_bs), metrics = jax.lax.scan(
            body, (zero, batch_stats),
            (jnp.arange(grad_accum), micro),
        )
        with jax.named_scope("grad_accum"):
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        metrics = jax.tree.map(
            lambda m: m.sum(0) if m.ndim else m, metrics
        )
        metrics = {
            k: (v if k in sum_metrics else v / grad_accum)
            for k, v in metrics.items()
        }
        return grads, metrics, new_bs

    def train_step(state: TrainState, batch: dict, rng: jax.Array):
        rngs = (
            {"dropout": jax.random.fold_in(rng, state.step)} if dropout else None
        )
        grads, metrics, new_bs = grads_and_metrics(
            state.params, state.batch_stats, batch, rngs
        )
        # what follows the gradient has no module to name it in a device
        # trace (obs/xprof.py): `grad_clip` is the cast and the global
        # norm (the metric; the clipping of `make_optimizer` needs the
        # same one, runs inside the optax chain and shows as
        # `optimizer/grad_clip`), `optimizer` the update and its
        # application
        with jax.named_scope("grad_clip"):
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            batch_stats=new_bs,
        )
        metrics = dict(metrics)
        with jax.named_scope("grad_clip"):
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    return jax.jit(
        train_step,
        donate_argnums=(0,) if donate else (),
        out_shardings=(sharding.tree, replicated),
    )


def make_eval_step(eval_fn: Callable, sharding: StateSharding):
    """`(state, batch) -> metrics`, compiled, metrics replicated.

    eval_fn(params, batch_stats, batch) -> metrics dict."""
    replicated = NamedSharding(sharding.mesh, P())

    def step(state: TrainState, batch: dict):
        return eval_fn(state.params, state.batch_stats, batch)

    return jax.jit(step, out_shardings=replicated)
