"""Honest wall-clock timing under JAX's async dispatch.

Reference timing brackets every measurement with
`torch.cuda.synchronize()` (`Phase 1/benchmarking.py:37-49`,
`compilation_optimization.py:105-111`). JAX dispatches asynchronously:
a timer stopped without a fence measures the enqueue, not the compute.
Two tools, both used by every benchmark in the tree:

1. **Host-fetch fencing** (`host_fence`): fetch a scalar reduction of
   the output tree to the host. A timer stopped after `host_fence` has
   provably waited for the compute feeding it (and the scalar doubles
   as a finiteness probe).
2. **Chained, data-dependent iteration** (`time_chained`): K iterations
   of the measured function run *inside one jit*, each serialized
   against the previous via `lax.optimization_barrier` (or by threading
   outputs into inputs), so no runtime can overlap or elide them.
   Timing two chain lengths and taking the slope removes the fixed
   dispatch overhead from the
   per-iteration number — the standard two-point method.

`time_fn` (per-call latency, host-fenced) remains for coarse epoch
timing where per-call overhead is genuinely part of the cost.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _scalar_probe(tree: Any) -> jax.Array:
    """One float32 scalar that consumes EVERY element of every leaf.

    A full reduction, deliberately: a cheaper probe (slicing one
    element) lets XLA fuse the slice into the producer and dead-code-
    eliminate the rest of the measured op — verified on this backend
    (an elementwise add "ran" at petabytes/s). Consuming all elements
    makes elision impossible; the reduction's own cost only matters in
    barrier-mode chains, where callers account for it (threaded chains
    probe once, after the timed region)."""
    total = jnp.float32(0)
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "dtype") or leaf.size == 0:
            continue
        if jnp.issubdtype(leaf.dtype, jnp.bool_):
            leaf = leaf.astype(jnp.int32)
        if jnp.issubdtype(leaf.dtype, jnp.number):
            total = total + jnp.sum(leaf).astype(jnp.float32)
    return total


def host_fence(tree: Any = None) -> float:
    """Fence that a lazy backend cannot fake: fetch a scalar reduction
    of `tree` to the host and return it. With no argument, falls back to
    `jax.effects_barrier()` (best-effort)."""
    if tree is None:
        jax.effects_barrier()
        return 0.0
    return float(jax.device_get(_scalar_probe(tree)))


def sync(tree: Any = None) -> None:
    """Wait for `tree` (or all in-flight work) to finish."""
    if tree is None:
        jax.effects_barrier()
    else:
        host_fence(tree)


@dataclasses.dataclass
class TimingResult:
    mean_ms: float
    std_ms: float
    min_ms: float
    median_ms: float
    iters: int
    times_ms: list[float]

    def throughput(self, items_per_call: int) -> float:
        """items/s at the mean latency (reference computes samples/s the
        same way — baseline_performance.ipynb cell 0:164-166)."""
        return items_per_call / (self.mean_ms / 1e3)


def time_fn(
    fn: Callable[..., Any],
    *args: Any,
    warmup: int = 3,
    iters: int = 20,
    **kwargs: Any,
) -> TimingResult:
    """Per-call latency with warmup and a host-fetch fence per iteration.

    Includes per-call dispatch overhead (which on a remote backend can
    dominate for small ops) — use `time_chained` for kernel-level
    numbers."""
    for _ in range(warmup):
        host_fence(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        host_fence(out)
        times.append((time.perf_counter() - t0) * 1e3)
    arr = np.asarray(times)
    return TimingResult(
        mean_ms=float(arr.mean()),
        std_ms=float(arr.std()),
        min_ms=float(arr.min()),
        median_ms=float(np.median(arr)),
        iters=iters,
        times_ms=times,
    )


@dataclasses.dataclass
class ChainedTimingResult:
    """Per-iteration time from two chain lengths (k1 < k2).

    `per_iter_ms` is the slope ((t2-t1)/(k2-k1)) — fixed launch/RPC
    overhead removed; this is the sustained kernel time. `amortized_ms`
    is t2/k2 — a conservative upper bound that still contains 1/k2 of
    the overhead. `overhead_ms` is the fixed cost estimate. `probe`
    is the fetched scalar — callers should check it is finite."""

    per_iter_ms: float
    amortized_ms: float
    overhead_ms: float
    k1: int
    k2: int
    t1_ms: float
    t2_ms: float
    probe: float

    def throughput(self, items_per_call: int) -> float:
        return items_per_call / (self.per_iter_ms / 1e3)


def _build_chain(
    fn: Callable[..., Any], length: int, n_thread: int
) -> Callable[..., jax.Array]:
    """A jitted function running `fn` `length` times, serialized.

    If `n_thread > 0`, the first `n_thread` outputs of `fn` replace the
    first `n_thread` args each iteration (natural state threading, e.g.
    a train step): every element of each iteration's output is consumed
    by the next, so nothing can be elided, and the only probe is one
    full-sum of the final carry *after* the timed iterations.

    Otherwise args are constant: each iteration's output is consumed by
    a full-sum probe (preventing dead-code elimination) and the next
    call is pinned after it via `lax.optimization_barrier`. The
    reduction rides along with the measured op; for elementwise ops
    prefer a threaded chain, which has zero per-iteration overhead."""

    @jax.jit
    def chained(*args):
        if n_thread:
            def body(carry, _):
                out = fn(*carry)
                new_head = out if n_thread > 1 else (out,)
                nxt = tuple(new_head[:n_thread]) + tuple(carry[n_thread:])
                return nxt, ()

            final, _ = lax.scan(body, tuple(args), None, length=length)
            return _scalar_probe(final[:n_thread])

        def body(carry, _):
            cur_args, acc = carry
            out = fn(*cur_args)
            probe = _scalar_probe(out)
            # tie the (unchanged) args to this iteration's output so
            # the next call cannot start, or be CSE'd, before it
            nxt, _p = lax.optimization_barrier((tuple(cur_args), probe))
            return (nxt, acc + probe), ()

        (_, acc), _ = lax.scan(
            body, (tuple(args), jnp.float32(0)), None, length=length
        )
        return acc

    return chained


def time_chained(
    fn: Callable[..., Any],
    *args: Any,
    k1: int = 8,
    k2: int = 24,
    reps: int = 3,
    n_thread: int = 0,
    min_window_s: float = 0.1,
    max_k2: int = 1024,
) -> ChainedTimingResult:
    """Sustained per-iteration time of `fn(*args)` via two chain lengths.

    Each chain is one jit containing k data-dependent iterations; the
    timer is fenced by fetching the chain's scalar probe to the host.
    Chain lengths auto-grow until the t2-t1 window exceeds
    `min_window_s`, so dispatch jitter cannot swamp the slope for
    small ops. Returns the
    slope-based per-iteration time (see ChainedTimingResult)."""
    if not (0 < k1 < k2):
        raise ValueError(f"need 0 < k1 < k2, got {k1=} {k2=}")

    def measure(k1: int, k2: int) -> tuple[float, float, float]:
        c1 = _build_chain(fn, k1, n_thread)
        c2 = _build_chain(fn, k2, n_thread)
        probe = float(jax.device_get(c1(*args)))  # compile + warm
        float(jax.device_get(c2(*args)))

        def best(c) -> float:
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(jax.device_get(c(*args)))
                ts.append(time.perf_counter() - t0)
            return min(ts)

        return best(c1), best(c2), probe

    t1, t2, probe = measure(k1, k2)
    while (t2 - t1) < min_window_s and k2 < max_k2:
        window = max(t2 - t1, 1e-4)
        factor = min(max_k2 // k2, max(2, int(min_window_s / window) + 1))
        if factor < 2:
            break
        k1, k2 = k1 * factor, k2 * factor
        t1, t2, probe = measure(k1, k2)

    slope = (t2 - t1) / (k2 - k1)
    if slope <= 0:  # noise swamped the difference; fall back to amortized
        slope = t2 / k2
    return ChainedTimingResult(
        per_iter_ms=slope * 1e3,
        amortized_ms=t2 / k2 * 1e3,
        overhead_ms=max(0.0, (t1 - k1 * slope)) * 1e3,
        k1=k1,
        k2=k2,
        t1_ms=t1 * 1e3,
        t2_ms=t2 * 1e3,
        probe=probe,
    )
