"""Train state: params + optimizer state + BN stats, laid out on the mesh.

The reference's equivalent is implicit — model params live inside the
DDP/FSDP wrapper, optimizer state inside `torch.optim.AdamW`, and the
layout (replicated vs sharded) is a property of which wrapper was used.
Here the state is one explicit pytree whose leaves carry `NamedSharding`s,
so the same `TrainState` serves DP (all-replicated), FSDP (param/opt
sharded), and TP — the difference is only the sharding tree built by
`hyperion_tpu.parallel`.

Init is performed *under jit with out_shardings* so a model too big for
one host is born sharded (FSDP materialized params shard-by-shard at wrap
time for the same reason — distributed_utils.py:328-332).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hyperion_tpu.parallel.partition import (
    Rule,
    named_shardings,
    shardings_like,
)
from hyperion_tpu.precision.policy import Policy, get_policy


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any  # {} for models without BN


@dataclasses.dataclass(frozen=True)
class StateSharding:
    """Sharding pytree mirroring TrainState, plus the mesh it lives on."""

    mesh: Mesh
    tree: TrainState  # leaves are NamedShardings

    @property
    def params(self):
        return self.tree.params


def make_optimizer(
    learning_rate: float,
    weight_decay: float = 0.0,
    grad_clip_norm: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.999,
    schedule: str = "constant",
    warmup_steps: int = 0,
    total_steps: int = 0,
) -> optax.GradientTransformation:
    """AdamW matching the reference's optimizers (AdamW everywhere —
    distributed_utils.py:161,231,334,503) with optional global-norm
    clipping (the FSDP loops' clip_grad_norm_(1.0), :351,522).

    Beyond reference parity (fixed LR there), `schedule` adds the
    standard decays: "cosine" (to 0 over `total_steps`) and
    "warmup_cosine" (linear 0 → lr over `warmup_steps`, then cosine).
    Schedules are pure functions of the optimizer step count, so they
    live inside the jitted update — no host involvement per step — and
    resume correctly from a checkpointed opt_state."""
    if schedule == "constant":
        lr = learning_rate
    elif schedule in ("cosine", "warmup_cosine"):
        if total_steps <= 0:
            raise ValueError(
                f"schedule {schedule!r} needs total_steps > 0 "
                f"(got {total_steps})"
            )
        if schedule == "cosine":
            lr = optax.cosine_decay_schedule(learning_rate, total_steps)
        else:
            if warmup_steps <= 0:
                raise ValueError(
                    "warmup_cosine needs warmup_steps > 0 (a zero "
                    "warmup silently degenerates into plain cosine — "
                    "pass --warmup-steps or use schedule='cosine')"
                )
            warmup = min(warmup_steps, total_steps - 1)
            lr = optax.warmup_cosine_decay_schedule(
                init_value=0.0, peak_value=learning_rate,
                warmup_steps=warmup, decay_steps=total_steps,
            )
    else:
        raise ValueError(
            f"unknown schedule {schedule!r} "
            "(constant | cosine | warmup_cosine)"
        )
    steps = []
    if grad_clip_norm and grad_clip_norm > 0:
        clip = optax.clip_by_global_norm(grad_clip_norm)

        def clip_update(updates, state, params=None):
            # the same transformation under a name a device trace shows
            # (`optimizer/grad_clip`, obs/xprof.py); its state is the
            # clip's own, so no checkpoint moves
            with jax.named_scope("grad_clip"):
                return clip.update(updates, state, params)

        steps.append(optax.GradientTransformation(clip.init, clip_update))
    steps.append(optax.adamw(lr, b1=b1, b2=b2, weight_decay=weight_decay))
    return optax.chain(*steps)


def _make_build(
    init_variables: Callable[[jax.Array], dict],
    optimizer: optax.GradientTransformation,
    policy: Policy,
) -> Callable[[jax.Array], TrainState]:
    def build(rng):
        variables = init_variables(rng)
        params = policy.cast_to_param(variables["params"])
        batch_stats = variables.get("batch_stats", {})
        opt_state = optimizer.init(params)
        return TrainState(
            step=jax.numpy.zeros((), jax.numpy.int32),
            params=params,
            opt_state=opt_state,
            batch_stats=batch_stats,
        )

    return build


def _spec_divisor(sharding: NamedSharding) -> int:
    """How many ways a leaf with this sharding splits across devices."""
    div = 1
    for entry in sharding.spec:
        for axis in (entry if isinstance(entry, tuple) else (entry,)):
            if axis is not None:
                div *= sharding.mesh.shape[axis]
    return div


def memory_plan(shapes: TrainState, sharding: StateSharding) -> dict:
    """Byte accounting for a planned TrainState: global and per-device
    totals by section, params additionally by dtype. Activations are
    deliberately excluded — they depend on batch/seq/remat, not on the
    state layout this module owns."""
    import numpy as np

    plan: dict = {"mesh": dict(sharding.mesh.shape)}
    per_device = 0.0
    total = 0
    for section in ("params", "opt_state", "batch_stats"):
        sec_total = 0
        sec_dev = 0.0
        leaves = jax.tree.leaves(getattr(shapes, section))
        shard_leaves = jax.tree.leaves(getattr(sharding.tree, section))
        for leaf, sh in zip(leaves, shard_leaves):
            nbytes = int(np.prod(leaf.shape)) * jax.numpy.dtype(leaf.dtype).itemsize
            sec_total += nbytes
            sec_dev += nbytes / _spec_divisor(sh)
        plan[f"{section}_gb"] = round(sec_total / 1e9, 4)
        total += sec_total
        per_device += sec_dev
    by_dtype: dict[str, int] = {}
    n_params = 0
    for leaf in jax.tree.leaves(shapes.params):
        n = int(np.prod(leaf.shape))
        n_params += n
        name = jax.numpy.dtype(leaf.dtype).name
        by_dtype[name] = by_dtype.get(name, 0) + n * jax.numpy.dtype(leaf.dtype).itemsize
    plan["param_count"] = n_params
    plan["params_by_dtype_gb"] = {
        k: round(v / 1e9, 4) for k, v in sorted(by_dtype.items())
    }
    plan["total_gb"] = round(total / 1e9, 4)
    plan["per_device_gb"] = round(per_device / 1e9, 4)
    return plan


def plan_train_state(
    init_variables: Callable[[jax.Array], dict],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    policy: str | Policy = "bf16",
    tp_rules: Sequence[Rule] | None = None,
    fsdp: bool = True,
    fsdp_min_size: int = 2**14,
) -> tuple[TrainState, StateSharding, dict]:
    """Shapes, shardings, and a memory plan — via `jax.eval_shape` only,
    so no device memory (or device at all) is touched. This is how a
    7B config is validated end-to-end (param tree, LoRA labels, TP/FSDP
    specs, optimizer masking) on a laptop CPU before a chip ever sees
    it; the trainers expose it as `--dry-init`."""
    policy = get_policy(policy)
    build = _make_build(init_variables, optimizer, policy)
    shapes = jax.eval_shape(build, rng)
    params_sh = named_shardings(
        shapes.params, mesh, tp_rules=tp_rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size
    )
    sharding = StateSharding(
        mesh=mesh,
        tree=TrainState(
            step=NamedSharding(mesh, P()),
            params=params_sh,
            opt_state=shardings_like(shapes.opt_state, shapes.params, params_sh, mesh),
            batch_stats=jax.tree.map(
                lambda _: NamedSharding(mesh, P()), shapes.batch_stats
            ),
        ),
    )
    return shapes, sharding, memory_plan(shapes, sharding)


def create_train_state(
    init_variables: Callable[[jax.Array], dict],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    policy: str | Policy = "bf16",
    tp_rules: Sequence[Rule] | None = None,
    fsdp: bool = True,
    fsdp_min_size: int = 2**14,
) -> tuple[TrainState, StateSharding]:
    """Build a sharded TrainState.

    `init_variables(rng)` returns the flax variables dict (params [+
    batch_stats]). The state is created *on-device, already sharded*:
    shapes come from `jax.eval_shape` (via `plan_train_state`), shardings
    from the parallel layer, and the actual init runs under jit with
    those out_shardings.
    """
    _, sharding, _ = plan_train_state(
        init_variables, optimizer, mesh, rng, policy=policy,
        tp_rules=tp_rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size,
    )
    build = _make_build(init_variables, optimizer, get_policy(policy))
    state = jax.jit(build, out_shardings=sharding.tree)(rng)
    return state, sharding
