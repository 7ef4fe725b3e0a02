#!/usr/bin/env python
"""Isolate the 8192^2 bf16 matmul MFU gap (89.2% vs 97.4% at 4096^2).

Hypothesis (VERDICT r3 weak #1): the ~0.68 ms/iter gap to nominal peak
at 8192 is carry-copy + non-overlapped HBM streaming of the scan-threaded
chain, not matmul tiling. Each variant times the same data-dependent
c@b chain built a different way; all share the folded-rescale operand
(no per-iteration epilogue). Run on the real chip:

    python scripts/mfu_probe.py [--size 8192] [--k 48]

Variants:
  scan       lax.scan threading c (the current hw_explore shape)
  unroll     python-unrolled chain inside one jit (no scan machinery,
             XLA sees k literal dots and can software-pipeline across them)
  donate     scan chain, but the jit donates the carry operand so XLA
             may alias the 128 MB output into the input buffer
  dimnum     dot_general with (t, nt) dimension numbers (c.T layout),
             checking whether the default row-major streaming is the cost
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

# run as `python scripts/mfu_probe.py`: script dir, not the repo root,
# is sys.path[0] — add the root so hyperion_tpu imports
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=8192)
    ap.add_argument("--k", type=int, default=48)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    n, k = args.size, args.k
    key0, keyb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(key0, (n, n), jnp.bfloat16)
    b = jax.random.normal(keyb, (n, n), jnp.bfloat16) * (1.0 / n ** 0.5)

    def probe_time(fn, *ops, reps=args.reps):
        """min-of-reps wall time of fn(*ops), host-fence by scalar fetch."""
        float(jax.device_get(fn(*ops)))  # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(jax.device_get(fn(*ops)))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def tflops(per_iter_s: float) -> float:
        return (2 * n**3 / per_iter_s) / 1e12

    results = {}

    def slope(build):
        """per-iter seconds via the two-chain-length slope."""
        k1, k2 = max(2, k // 3), k
        t1 = probe_time(build(k1), a, b)
        t2 = probe_time(build(k2), a, b)
        return (t2 - t1) / (k2 - k1)

    # -- scan (current shape) ------------------------------------------
    def build_scan(length):
        @jax.jit
        def chain(c, b):
            def body(carry, _):
                return carry @ b, ()
            out, _ = lax.scan(body, c, None, length=length)
            return jnp.sum(out, dtype=jnp.float32)
        return chain

    results["scan"] = tflops(slope(build_scan))

    # -- unrolled ------------------------------------------------------
    def build_unroll(length):
        @jax.jit
        def chain(c, b):
            for _ in range(length):
                c = c @ b
            return jnp.sum(c, dtype=jnp.float32)
        return chain

    results["unroll"] = tflops(slope(build_unroll))

    # -- donated scan carry -------------------------------------------
    def build_donate(length):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def chain(c, b):
            def body(carry, _):
                return carry @ b, ()
            out, _ = lax.scan(body, c, None, length=length)
            return jnp.sum(out, dtype=jnp.float32)
        return chain

    def slope_donate():
        k1, k2 = max(2, k // 3), k

        def timed(chain):
            # donation consumes the carry: EVERY call (warm-up included)
            # needs its own copy, made and fenced before the timer starts
            def once():
                c = jnp.copy(a)
                jax.block_until_ready(c)
                t0 = time.perf_counter()
                float(jax.device_get(chain(c, b)))
                return time.perf_counter() - t0

            once()  # compile + warm
            return min(once() for _ in range(args.reps))

        t1 = timed(build_donate(k1))
        t2 = timed(build_donate(k2))
        return (t2 - t1) / (k2 - k1)

    results["donate"] = tflops(slope_donate())

    # -- dot_general, contract on c's leading dim (transposed layout) --
    def build_dimnum(length):
        @jax.jit
        def chain(c, b):
            def body(carry, _):
                # (b.T @ carry).T == carry @ b with swapped operand order:
                # same math, different operand streaming order
                out = lax.dot_general(b, carry, (((0,), (1,)), ((), ())))
                return out.T, ()
            out, _ = lax.scan(body, c, None, length=length)
            return jnp.sum(out, dtype=jnp.float32)
        return chain

    results["dimnum"] = tflops(slope(build_dimnum))

    from hyperion_tpu.utils.chips import nominal_peak_tflops

    peak = nominal_peak_tflops("bfloat16")
    doc = {
        "size": n, "k": k,
        "tflops": {v: round(t, 2) for v, t in results.items()},
    }
    if peak:
        doc["mfu"] = {v: round(t / peak, 4) for v, t in results.items()}
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
