"""Collective-communication sanity check — the `test_nccl.py` equivalent.

Reference: `02_development/test_nccl.py:8-47` inits a NCCL process group
with a 30-s timeout, all-reduces `ones(1) * rank`, verifies the result is
`sum(range(world))`, and exits 1 on failure; the README prescribes running
it before any big job.

TPU-native version: build a 1-axis mesh over every device and drive each
collective XLA relies on — psum (all-reduce), all_gather, psum_scatter
(reduce-scatter), ppermute (the ring primitive) — through `jax.shard_map`,
verifying numerics per device. This exercises ICI (and DCN on multi-slice)
exactly where training traffic will flow.

CLI:  python -m hyperion_tpu.runtime.comm_check [--host-only]

`--host-only` exercises just the C++ host-coordination layer (handshake
+ named barriers + liveness) across RANK/WORLD_SIZE processes without
touching devices — the pre-flight the reference ran `test_nccl.py` for,
usable before committing chips to a job.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from hyperion_tpu.runtime import dist

_AXIS = "ring"


def _checks(n: int):
    """Per-collective (fn, expected) pairs on input x[i] = i (one scalar
    row per device)."""
    idx = np.arange(n, dtype=np.float32)
    return {
        "psum": (
            lambda x: jax.lax.psum(x, _AXIS),
            np.full((n, 1), idx.sum(), np.float32),
        ),
        "pmax": (
            lambda x: jax.lax.pmax(x, _AXIS),
            np.full((n, 1), idx.max(), np.float32),
        ),
        "all_gather": (
            lambda x: jax.lax.all_gather(x[0], _AXIS),
            np.tile(idx.reshape(n, 1), (n, 1)).reshape(n, n, 1)[:, :, 0],
        ),
        "psum_scatter": (
            # Each device contributes a length-n row of its index; the
            # scatter leaves shard i holding sum_j j = n(n-1)/2.
            lambda x: jax.lax.psum_scatter(
                jnp.tile(x, (1, n)).reshape(n * x.shape[0]), _AXIS, tiled=True
            ),
            np.full((n, 1), idx.sum(), np.float32),
        ),
        "ppermute_ring": (
            lambda x: jax.lax.ppermute(
                x, _AXIS, perm=[(i, (i + 1) % n) for i in range(n)]
            ),
            np.roll(idx, 1).reshape(n, 1),
        ),
    }


def comm_check(devices=None, verbose: bool = True) -> bool:
    """Run every collective over all devices; return True iff all pass."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), (_AXIS,))
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    ok = True
    for name, (fn, expected) in _checks(n).items():
        t0 = time.perf_counter()
        try:
            out = jax.jit(
                jax.shard_map(fn, mesh=mesh, in_specs=P(_AXIS), out_specs=P(_AXIS))
            )(x)
            out = np.asarray(jax.block_until_ready(out))
            good = np.allclose(out.reshape(expected.shape), expected)
        except Exception as e:  # noqa: BLE001 — a failed collective must not kill the probe
            good, out = False, repr(e)
        ok &= good
        if verbose:
            dt = (time.perf_counter() - t0) * 1e3
            status = "OK" if good else f"FAIL (got {out})"
            print(f"[comm_check] {name:>14s} over {n} devices: {status} ({dt:.1f} ms)")
    return ok


def host_check(rounds: int = 3) -> bool:
    """Host-layer-only pre-flight: handshake (dist.setup), named
    barriers, liveness. Device-free, so it runs before chips are
    committed. Single-process runs report and pass trivially."""
    import os

    os.environ.setdefault("HYPERION_SKIP_JAX_INIT", "1")
    try:
        dist.setup()
        # same env precedence as dist.setup — a JAX_NUM_PROCESSES launch
        # must not trivially pass the pre-flight
        world = int(dist._env_first(dist._ENV_NUM_PROCESSES) or 1)
        if world <= 1:
            print("[comm_check] host-only: single process, nothing to sync")
            return True
        for i in range(rounds):
            dist.host_barrier(f"host_check_{i}", timeout_s=30.0)
        alive = dist.peers_alive()
        print(f"[comm_check] host-only rank {dist.process_index()}/{world}: "
              f"{rounds} barriers OK, {alive} hosts alive")
        dist.cleanup()
        return alive == world
    except Exception as e:  # noqa: BLE001 — report, exit 1, like test_nccl
        print(f"[comm_check] host-only FAILED: {e}")
        return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--host-only", action="store_true",
                   help="exercise only the C++ host coordinator "
                        "(no devices needed)")
    args = p.parse_args(argv)

    if args.host_only:
        ok = host_check()
        print(f"[comm_check] {'HOST LAYER OK' if ok else 'FAILURE'}")
        return 0 if ok else 1

    dist.setup()
    n = len(jax.devices())
    print(
        f"[comm_check] process {dist.process_index()}/{dist.process_count()}, "
        f"{n} global devices, backend={jax.default_backend()}"
    )
    ok = comm_check()
    dist.cleanup()
    print(f"[comm_check] {'ALL COLLECTIVES PASSED' if ok else 'FAILURE'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
