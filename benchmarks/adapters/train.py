"""Drives the trainer's own pieces in this process, as
`train_language_model` assembles them for `language_fsdp`: a one-device
mesh, `create_train_state`, the trainer's loss, `make_train_step(...,
dropout=True)`, batches from `ShardedBatches` behind a `Prefetcher`.
Left out on purpose: epochs, checkpoints, validation, CSVs — the window
holds optimizer steps and nothing else."""

from __future__ import annotations

import itertools
import math
import time

import numpy as np

from benchmarks import costs, trace_reduce

CLOCK = time.monotonic
# step-0 loss against what the initialisers imply: the final LayerNorm
# hands the head unit-variance features, the head is normal(0.02) wide
# and its bias 0, so a logit has variance d x 0.02^2 and the mean loss is
# ln(vocab) + half of that (10.979 at d 768: measured 10.969-10.979 on
# the chip in PR 23; ln 50257 alone is 10.825)
HEAD_INIT_STD = 0.02
LOSS_AT_INIT_SLACK = 0.05
# The loss at the initial parameters says nothing of the blocks (the
# head's initialisation fixes it), so `correct` holds the system to the
# plain reference in what the blocks and the backward pass decide, with
# dropout off on both sides: the logits of the first batch's first
# CHECK_ROWS sequences, and CHECK_STEPS optimizer steps on the first
# batch, in the loss before each step and in the direction every module's
# parameters have moved. The slacks stand between what bf16 reads and
# what a fault reads (PERF.md, PR 23, has both).
CHECK_ROWS = 2
CHECK_STEPS = 8
LOGITS_SLACK = 0.03           # RMS difference over the logits' std
LOSS_SLACK = 0.003            # largest difference of a loss
COSINE_FLOOR = 0.98           # least cosine of a module's update
SEQUENCES = 256               # distinct sequences, then they repeat
IN_FLIGHT = 2                 # steps the host dispatches ahead


def _pieces(cell: dict, mesh, seed: int):
    """(model, init_variables, optimizer, loss_fn, policy)."""
    import jax.numpy as jnp

    from hyperion_tpu.models.transformer_lm import (
        TransformerLM,
        gpt2_lm_config,
    )
    from hyperion_tpu.precision.policy import get_policy
    from hyperion_tpu.train.losses import next_token_loss
    from hyperion_tpu.train.state import make_optimizer

    m = cell["model"]
    policy = get_policy(cell["precision"])
    model = TransformerLM(gpt2_lm_config(
        vocab_size=m["vocab_size"], d_model=m["n_embd"], n_heads=m["n_head"],
        n_layers=m["n_layer"], ff_dim=4 * m["n_embd"],
        max_len=m["n_positions"], dropout=cell["dropout"], remat=False,
        dtype=jnp.dtype(policy.compute_dtype).name,
        attention_impl="xla", norm_impl="xla"))
    optimizer = make_optimizer(**cell["optimizer"])

    def init_variables(rng):
        return {"params": model.init_params(rng)}

    def loss_fn(params, batch_stats, batch, rngs):
        logits = model.apply(
            {"params": params}, batch["input_ids"],
            padding_mask=batch["attention_mask"],
            deterministic=rngs is None, rngs=rngs)
        loss = next_token_loss(logits, batch["input_ids"],
                               batch["attention_mask"], impl="xla")
        return loss, ({"loss": loss}, batch_stats)

    return model, init_variables, optimizer, loss_fn, policy


def _state_and_step(cell, mesh, seed, dropout=True):
    """(state, step, rng, model); `dropout=False` is the step the check
    after the window takes, the same code with the masks off."""
    import jax

    from hyperion_tpu.parallel.partition import TRANSFORMER_TP_RULES
    from hyperion_tpu.train.state import create_train_state
    from hyperion_tpu.train.step import make_train_step

    model, init_variables, optimizer, loss_fn, policy = _pieces(
        cell, mesh, seed)
    rng = jax.random.key(seed & 0x7FFFFFFF)
    state, sharding = create_train_state(
        init_variables, optimizer, mesh, rng, policy=policy,
        tp_rules=TRANSFORMER_TP_RULES, fsdp=True)
    step = make_train_step(loss_fn, optimizer, sharding, grad_accum=1,
                           donate=True, dropout=dropout)
    return state, step, rng, model


def _data(cell, seed) -> dict:
    rng = np.random.default_rng([seed, 3])
    n, T = SEQUENCES, cell["seq_len"]
    return {"input_ids": rng.integers(0, cell["model"]["vocab_size"], (n, T),
                                      dtype=np.int32),
            "attention_mask": np.ones((n, T), np.int32)}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.data.prefetch import Prefetcher
    from hyperion_tpu.data.sharding import ShardedBatches
    from hyperion_tpu.runtime.mesh import MeshSpec, make_mesh

    m = cell["model"]
    B, T = cell["global_batch"], cell["seq_len"]
    mesh = make_mesh(MeshSpec(data=1, fsdp=-1), devices=jax.devices()[:1])
    t = CLOCK()
    state, step, rng, _ = _state_and_step(cell, mesh, seed)
    jax.block_until_ready(state)
    say(state_s=CLOCK() - t, state_bytes=sum(
        x.nbytes for x in jax.tree.leaves(state)))
    batches = ShardedBatches(_data(cell, seed), B, mesh, shuffle=True,
                             seed=seed & 0x7FFFFFFF)

    def forever():
        for epoch in itertools.count():
            yield from batches.epoch(epoch)

    with Prefetcher(forever(), depth=2) as feed:
        # warm-up: the step compiles (or comes from the cache) on the
        # first batch; its loss is the loss at the initial parameters
        first = next(feed)
        t = CLOCK()
        state, metrics = step(state, first, rng)
        loss0 = float(metrics["loss"])
        say(first_step_s=CLOCK() - t, loss_at_init=loss0)
        state, metrics = step(state, next(feed), rng)
        jax.block_until_ready(metrics)

        step_s, wait_s, losses, pending = [], [], [], []

        def one_step(close: bool, tracing: bool = False) -> None:
            nonlocal state
            t = CLOCK()
            with trace_reduce.span(tracing, "bench.next_batch"):
                batch = next(feed)
            wait_s.append(CLOCK() - t)
            with trace_reduce.span(tracing, "bench.train_step"):
                state, metrics = step(state, batch, rng)
                pending.append(metrics["loss"])
                # fetching a loss closes its step; the host stays at
                # most `in_flight` steps ahead of the device
                while len(pending) > (0 if close else IN_FLIGHT):
                    losses.append(float(pending.pop(0)))
            if close:
                step_s.append(CLOCK() - t)

        w0 = CLOCK()
        while CLOCK() - w0 < seconds:
            # the traced run closes every step, so that a step's
            # host-clock time is its own; the other keeps the device fed
            one_step(close=trace)
        losses += [float(x) for x in pending]   # closes the last steps
        pending.clear()
        w1 = CLOCK()
        steps = len(losses)

        # the trace, after the window (starting and stopping the
        # profiler stalls the loop), of steps dispatched ahead as in the
        # run that reports the rate
        reduced, traced_steps = None, 0
        if trace:
            with trace_reduce.capture(trace_dir):
                until = CLOCK() + cell["trace_s"]
                while CLOCK() < until:
                    one_step(close=False, tracing=True)
                    traced_steps += 1
                losses += [float(x) for x in pending]
            reduced = trace_reduce.reduce_dir(trace_dir)
            del wait_s[steps:]
    peak = _peak_bytes(step, state, first, rng)
    say(steps=steps, window_s=w1 - w0, loss_last=losses[-1],
        input_wait_total_s=sum(wait_s))
    del state
    expected = math.log(m["vocab_size"]) + m["n_embd"] * HEAD_INIT_STD ** 2 / 2
    finite = [math.isfinite(x) for x in [loss0, *losses]]
    correct = (all(finite)
               and abs(loss0 - expected) <= LOSS_AT_INIT_SLACK
               and _agrees_with_reference(cell, mesh, seed, first, say))
    return {
        "correct": bool(correct), "attempted": steps,
        "failed": finite[1:steps + 1].count(False),
        "measured": {"train_tok_per_s": steps * B * T / (w1 - w0),
                     "setup_s": w0 - t_start},
        "peak_bytes": peak,
        "ctx": {"cell": cell, "trace": reduced,
                "series": {"step_s": step_s, "input_wait_s": wait_s},
                "traced_steps": traced_steps,
                "flops_per_step":
                    B * T * costs.gpt_train_flops_per_token(m, T)},
    }


def _peak_bytes(step, state, batch, rng) -> int:
    """The allocator's peak where it counts a running program's
    temporaries; where it does not (PERF.md, PR 21: `peak_bytes_in_use`
    stayed at the arguments' size), the step's arguments plus the
    temporaries its compiler reports."""
    import jax

    seen = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    mem = step.lower(state, batch, rng).compile().memory_analysis()
    if mem is None:
        return seen
    return max(seen, mem.argument_size_in_bytes + mem.temp_size_in_bytes)


def _agrees_with_reference(cell, mesh, seed, batch, say) -> bool:
    """After the window, outside every timing: the seed gives the
    initial state again, and the system (its model's forward pass and
    its train step, dropout off) and the plain reference both start
    from it on `batch`."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt

    t = CLOCK()
    state, step, rng, model = _state_and_step(cell, mesh, seed, dropout=False)
    start = jax.tree.map(jnp.copy, state.params)    # the step donates
    ids = batch["input_ids"]

    @jax.jit
    def forward(params, ids):
        return model.apply({"params": params}, ids,
                           padding_mask=jnp.ones_like(ids), deterministic=True)

    rows = ids[:CHECK_ROWS]
    logits_error = gpt.logits_error(forward(start, rows),
                                    gpt.logits(start, rows))
    losses = []
    for _ in range(CHECK_STEPS):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    want, params = gpt.train(start, [ids] * CHECK_STEPS, rows=CHECK_ROWS,
                             **cell["optimizer"])
    cosines = gpt.update_cosines(start, state.params, params)
    numbers = {"logits_error": logits_error,
               "loss_gap": max(abs(a - b) for a, b in zip(losses, want)),
               "least_cosine": min(cosines.values())}
    say(check_s=CLOCK() - t, **numbers, check_losses=losses,
        reference_losses=want,
        least_cosines=sorted(cosines.items(), key=lambda kv: kv[1])[:5])
    return agrees(**numbers)


def agrees(logits_error: float, loss_gap: float, least_cosine: float) -> bool:
    return (logits_error <= LOGITS_SLACK and loss_gap <= LOSS_SLACK
            and least_cosine >= COSINE_FLOOR)
