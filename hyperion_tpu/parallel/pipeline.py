"""GPipe-style pipeline parallelism over the mesh's `pipe` axis.

Reference status: **absent** — SURVEY §2.2's PP row records "No pipeline/
stage code anywhere" in the MI250X project; this module is beyond-parity
TPU headroom, built the way the hardware wants it rather than as a
wrapper class:

  * Each pipeline stage is one mesh coordinate along `pipe` and owns the
    stacked parameters of its contiguous slice of layers — a pytree
    whose leaves have leading shape [n_stages, layers_per_stage, ...],
    sharded `P('pipe')`. No wrapper objects, no per-stage processes:
    parallelism is a layout decision, exactly like the FSDP/TP rules in
    `parallel.partition`.
  * The schedule is a `lax.scan` over S+M-1 ticks (S stages, M
    microbatches). At tick t, stage s computes microbatch t-s; finished
    activations hop one stage downstream via `lax.ppermute` over ICI.
    All of it lives inside one jit — XLA sees a static loop and overlaps
    the ppermute with the next tick's compute where the hardware allows.
  * The first stage feeds from the microbatched input buffer, the last
    stage writes into an output buffer; bubble ticks (t-s outside
    [0, M)) compute on zeros and their results are never written — the
    standard GPipe bubble, cost (S-1)/(S+M-1) of the schedule.

Differentiable end to end: ppermute's transpose is the reverse
ppermute, so `jax.grad` through `gpipe_apply` yields the backward
pipeline automatically (activations recompute under the caller's remat
policy like any other jitted graph).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hyperion_tpu.utils import compat
from jax.sharding import Mesh, PartitionSpec as P

from hyperion_tpu.runtime.mesh import AxisName


def stage_count(mesh: Mesh, axis_name: str = AxisName.PIPE) -> int:
    return mesh.shape[axis_name]


def _local_gpipe(
    stage_params: Any,
    xs: jax.Array,
    extras: Any,
    *,
    stage_fn: Callable[[Any, jax.Array, Any], jax.Array],
    axis_name: str,
    n_micro: int,
):
    """Runs inside shard_map. stage_params leaves: [1, lps, ...] (this
    stage's slice); xs: [M, mb, ...] microbatched inputs (replicated
    along `pipe`); extras: pytree of [M, ...] per-microbatch side inputs
    (e.g. padding masks), indexed — not rotated — because every device
    holds all of them. Returns [1, M, mb, ...]: this stage's output
    buffer; only the last stage's slice is meaningful."""
    params = jax.tree.map(lambda a: a[0], stage_params)
    n = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    last = n - 1
    perm = [(j, (j + 1) % n) for j in range(n)]

    # scan carries must hold the same varying-axes type as the rotating
    # activations (jax 0.9 shard_map tracks vma in loop carry types):
    # stage outputs vary over `pipe` (via params) AND the batch axes
    # (via xs), so the carry needs the union — over EVERY param leaf,
    # since in the fsdp-sharded layers path different leaves can vary
    # over different axes (fsdp, model) depending on their specs
    vma_set = set(compat.vma_of(xs))
    for leaf in jax.tree.leaves(params):
        vma_set |= set(compat.vma_of(leaf))
    vma = tuple(vma_set)
    pvary = functools.partial(compat.pvary, axes=vma)
    state0 = pvary(jnp.zeros(xs.shape[1:], xs.dtype))
    out0 = pvary(jnp.zeros(xs.shape, xs.dtype))

    def tick(carry, t):
        state, out = carry
        # stage s processes microbatch t-s at tick t
        m_in = jnp.clip(t - stage, 0, n_micro - 1)
        x_first = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        x = jnp.where(stage == 0, x_first, state)
        extra = jax.tree.map(
            lambda e: lax.dynamic_index_in_dim(e, m_in, 0, keepdims=False),
            extras,
        )
        y = stage_fn(params, x, extra)
        # the last stage finishes microbatch t-(S-1)
        widx = t - last
        valid = (stage == last) & (widx >= 0)
        slot = jnp.maximum(widx, 0)
        cur = lax.dynamic_index_in_dim(out, slot, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, cur), slot, 0
        )
        state = lax.ppermute(y, axis_name, perm)
        return (state, out), None

    (_, out), _ = lax.scan(
        tick, (state0, out0), jnp.arange(n + n_micro - 1)
    )
    return out[None]


def gpipe_apply(
    stage_fn: Callable[[Any, jax.Array, Any], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: int,
    extras: Any = None,
    axis_name: str = AxisName.PIPE,
    batch_axes: tuple[str, ...] | None = None,
    param_in_specs: Any = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Run `x` through the S-stage pipeline; returns same-shape output.

    stage_fn(params_stage, x_mb, extra_mb) -> y_mb must preserve the
    activation shape (repeated transformer blocks do). `stage_params`
    leaves are [S, layers_per_stage, ...] sharded over `axis_name`;
    `x` is [B, ...] with B divisible by n_microbatches; leaves of
    `extras` are [B, ...] side inputs that follow their microbatch.

    `rng` threads dropout noise through the rotating schedule: the key
    is split per microbatch and the split keys ride the (replicated)
    extras indexing, so at tick t stage s receives the key of the
    microbatch it is processing. stage_fn is then called as
    stage_fn(params, x_mb, extra_mb, rng_mb) and should fold in its own
    stage/layer indices (`lax.axis_index(axis_name)` is live inside).

    Memory note: the default in_spec `P(axis_name)` gathers each stage's
    FULL parameter slice (all its layers, all dims) onto its devices for
    the duration of the step — any fsdp/model sharding of NON-stage dims
    is undone inside the loop. For true FSDP-within-stage use
    `gpipe_apply_layers`, which keeps params sharded through the
    shard_map boundary (`param_in_specs`) and gathers one layer at a
    time inside the tick.
    """
    S = mesh.shape[axis_name]
    B = x.shape[0]
    M = n_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    batch_axes = AxisName.BATCH if batch_axes is None else batch_axes
    n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
    if mb % n_batch_shards:
        raise ValueError(
            f"microbatch size {mb} (= batch {B} / {M} microbatches) not "
            f"divisible by the {n_batch_shards}-way batch sharding "
            f"{batch_axes}; grow the batch or lower n_microbatches"
        )

    def to_micro(a):
        return a.reshape(M, mb, *a.shape[1:])

    xs = to_micro(x)
    # None stays None: tree.map treats it as an empty pytree, so specs
    # and indexing pass it through untouched (ring_attention's optional
    # pad uses the same pattern)
    extras = jax.tree.map(to_micro, extras)

    mb_spec = P(None, batch_axes)  # [M, mb@batch, ...]
    extras_specs = jax.tree.map(lambda _: mb_spec, extras)
    if rng is not None:
        # per-microbatch keys ride the same [M]-leading index as extras,
        # but replicated (every stage sees every microbatch's key and
        # picks the one for the microbatch it is on)
        extras = (extras, jax.random.split(rng, M))
        extras_specs = (extras_specs, P())
        user_fn = stage_fn
        rng_axes = batch_axes

        def stage_fn(params, x_mb, extra):  # noqa: F811 — deliberate wrap
            # each batch shard holds DIFFERENT samples, so its dropout
            # noise must differ too: fold the shard coordinates in
            # before the microbatch key reaches the stage (axis_index
            # of a size-1 axis is 0 — harmless)
            rng_mb = extra[1]
            for ax in rng_axes:
                rng_mb = jax.random.fold_in(rng_mb, lax.axis_index(ax))
            return user_fn(params, x_mb, extra[0], rng_mb)

    param_specs = (
        P(axis_name) if param_in_specs is None else param_in_specs
    )
    fn = jax.shard_map(
        functools.partial(
            _local_gpipe, stage_fn=stage_fn, axis_name=axis_name, n_micro=M
        ),
        mesh=mesh,
        in_specs=(param_specs, mb_spec, extras_specs),
        out_specs=P(axis_name, None, batch_axes),  # [S@pipe, M, mb@batch, ...]
    )
    out = fn(stage_params, xs, extras)  # [S, M, mb, ...]
    return out[-1].reshape(B, *x.shape[1:])


def _flatten_specs(specs: Any) -> list[P]:
    return jax.tree.flatten(specs, is_leaf=lambda s: isinstance(s, P))[0]


def _gather_plans(
    flat_params: list, flat_specs: list[P], axis_name: str,
    batch_axes: tuple[str, ...],
) -> list[tuple[tuple[int, tuple[str, ...]], ...]]:
    """Per leaf: ((layer-local dim, mesh axes to all_gather), ...).

    Leaf global layout is [S, lps, *body]; dim 0 must be the pipe axis
    and dim 1 (the layer axis the tick scans) must be unsharded —
    `partition_specs` guarantees both for stages/ leaves. Body dims
    shift by 2 once the pipe shard is peeled and the layer scan indexes
    the lps axis.

    Only axes the pipeline OUTPUT already varies over (the batch axes —
    fsdp rides there) may be gathered: an all_gather keeps its axis
    varying in shard_map's type system, and out_specs mentions only
    pipe + batch axes, so gathering e.g. the 'model' (TP) axis inside
    the tick cannot type-check. TP stage leaves belong on the classic
    whole-stage `gpipe_apply` path instead."""
    plans = []
    for leaf, spec in zip(flat_params, flat_specs):
        entries = tuple(spec) + (None,) * (np.ndim(leaf) - len(spec))
        if not entries or entries[0] != axis_name:
            raise ValueError(
                f"stage leaf spec {spec} must lead with the {axis_name!r} "
                "axis (stacked [S, lps, ...] layout)"
            )
        if len(entries) > 1 and entries[1] is not None:
            raise ValueError(
                f"stage leaf spec {spec} shards the layer axis (dim 1) — "
                "the per-layer pipeline scan needs it whole"
            )
        plan = []
        for d, e in enumerate(entries[2:]):
            if e is None:
                continue
            names = e if isinstance(e, tuple) else (e,)
            bad = [n for n in names if n not in batch_axes]
            if bad:
                raise ValueError(
                    f"stage leaf spec {spec} shards dim {d + 2} over "
                    f"{bad}, which the pipeline output does not vary "
                    f"over (batch axes: {batch_axes}) — per-layer gather "
                    "supports fsdp-style sharding only; use gpipe_apply "
                    "(whole-stage gather) for TP-sharded stages"
                )
            plan.append((d, tuple(names)))
        plans.append(tuple(plan))
    return plans


def gpipe_apply_layers(
    layer_fn: Callable[[Any, jax.Array, Any], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: int,
    param_specs: Any,
    extras: Any = None,
    axis_name: str = AxisName.PIPE,
    batch_axes: tuple[str, ...] | None = None,
    remat_layers: bool = True,
    rng: jax.Array | None = None,
) -> jax.Array:
    """GPipe with FSDP-within-stage: ZeRO-3 semantics inside the tick.

    `layer_fn(layer_params, x_mb, extra_mb) -> y_mb` is applied to each
    of the stage's lps layers in order. Unlike `gpipe_apply`, the stage
    params cross the shard_map boundary STILL SHARDED per `param_specs`
    (the same PartitionSpecs `parallel.partition` chose for the train
    state, e.g. P('pipe', None, 'fsdp')); each tick's layer scan
    all-gathers ONE layer's leaves along their fsdp/model-sharded dims
    right before use, so peak gathered memory is a single layer, not the
    whole stage. With `remat_layers` the gather+layer call sits under
    `jax.checkpoint`: backward re-gathers instead of keeping gathered
    buffers alive across the schedule — exactly FSDP's
    gather-on-use/free-after-use, expressed as layout + rematerialization
    (the gather's transpose is the grads' reduce-scatter, inserted by AD).

    With `rng`, layer_fn is called as layer_fn(layer, x, extra, rng_l)
    where rng_l is already folded with the microbatch, stage, and layer
    indices (dropout-ready).
    """
    flat, treedef = jax.tree.flatten(stage_params)
    flat_specs = _flatten_specs(param_specs)
    if len(flat_specs) != len(flat):
        raise ValueError(
            f"param_specs has {len(flat_specs)} leaves, stage_params "
            f"{len(flat)}"
        )
    plans = _gather_plans(
        flat, flat_specs, axis_name,
        AxisName.BATCH if batch_axes is None else batch_axes,
    )
    n_layers = jax.tree.leaves(stage_params)[0].shape[1]

    def apply_layer(h, layer, extra, rng_l):
        flat_layer = jax.tree.leaves(layer)
        full = jax.tree.unflatten(treedef, [
            _all_gather_dims(a, plan) for a, plan in zip(flat_layer, plans)
        ])
        if rng_l is None:
            return layer_fn(full, h, extra)
        return layer_fn(full, h, extra, rng_l)

    if remat_layers:
        apply_layer = jax.checkpoint(apply_layer)

    def stage_fn(params, x, extra, rng_mb=None):
        # params leaves [lps, ...] (pipe dim already peeled): scan layers
        rng_s = (
            None if rng_mb is None
            else jax.random.fold_in(rng_mb, lax.axis_index(axis_name))
        )

        def body(h, layer_i):
            layer, i = layer_i
            rng_l = None if rng_s is None else jax.random.fold_in(rng_s, i)
            return apply_layer(h, layer, extra, rng_l), None

        x, _ = lax.scan(body, x, (params, jnp.arange(n_layers)))
        return x

    return gpipe_apply(
        stage_fn, stage_params, x, mesh,
        n_microbatches=n_microbatches, extras=extras, axis_name=axis_name,
        batch_axes=batch_axes, param_in_specs=param_specs, rng=rng,
    )


def _all_gather_dims(a: jax.Array, plan: tuple) -> jax.Array:
    for d, names in plan:
        for ax in names:
            a = lax.all_gather(a, ax, axis=d, tiled=True)
    return a
