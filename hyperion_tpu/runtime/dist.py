"""Multi-host runtime bootstrap — the `setup()`/`cleanup()` equivalent.

Reference: `02_development/distributed_utils.py:96-125` does
`dist.init_process_group("nccl", init_method="env://", timeout=5min)` per
GPU process plus `torch.cuda.set_device(rank % ndev)`.  The TPU-native
shape is one process per *host*: `jax.distributed.initialize` performs
the coordinator rendezvous (the env:// analogue), after which every
process sees the global device set and collectives ride ICI/DCN.

Single-host runs (the common dev/bench case, and everything the
reference's `torchrun --standalone` did) need no rendezvous at all —
`setup()` is a no-op there, by design rather than accident.

Multi-process runs additionally stand up the in-tree C++ host
coordinator (`native/coord.cpp` via `runtime.native_coord`) *before*
JAX's rendezvous: a pre-flight handshake with a hard timeout (the
reference's `init_process_group(timeout=5min)` semantics,
`distributed_utils.py:111`), named barriers independent of any device
computation (the reference's `dist.barrier()` around FSDP checkpoint IO,
`:369,405`), and fail-fast peer-death detection instead of the hung
collective the reference's disabled NCCL watchdog would have left
(`run_language_fsdp.sh:10`). Set `HYPERION_HOST_COORD=0` to disable;
`HYPERION_SKIP_JAX_INIT=1` runs the host layer alone (pre-flight checks
and the 2-process CPU tests).
"""

from __future__ import annotations

import datetime
import logging
import os

import jax

log = logging.getLogger(__name__)

_INITIALIZED = False
_HOST_COORD = None
_HOST_RANK: int | None = None
_NUM_PROCESSES: int | None = None  # resolved by setup() (arg or env)
_JAX_SKIPPED = False  # host-coordination-only mode: never touch the backend

# torchrun-style env compatibility: the reference reads RANK/WORLD_SIZE
# (run_distributed.py:73-79); JAX's native names are also honored.
_ENV_PROCESS_ID = ("JAX_PROCESS_ID", "PROCESS_ID", "RANK")
_ENV_NUM_PROCESSES = ("JAX_NUM_PROCESSES", "NUM_PROCESSES", "WORLD_SIZE")
_ENV_COORDINATOR = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS", "MASTER_ADDR")

DEFAULT_COORD_PORT = 29500  # reference default MASTER_PORT (distributed_utils.py:103-110)
DEFAULT_TIMEOUT_S = 300  # reference PG init timeout (distributed_utils.py:111)


def _env_first(names) -> str | None:
    for n in names:
        v = os.environ.get(n)
        if v:
            return v
    return None


def setup(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    init_timeout_s: int = DEFAULT_TIMEOUT_S,
) -> None:
    """Initialize the multi-host runtime if (and only if) this run spans
    more than one process. Safe to call unconditionally, like the
    reference's `setup(rank, world)`."""
    global _INITIALIZED, _HOST_COORD, _HOST_RANK, _JAX_SKIPPED
    global _NUM_PROCESSES
    if _INITIALIZED:
        return
    num_processes = num_processes or int(_env_first(_ENV_NUM_PROCESSES) or 1)
    _NUM_PROCESSES = num_processes  # args must win over env in skip-jax mode
    if num_processes <= 1:
        return  # single-host: mesh over local devices, no rendezvous
    process_id = (
        process_id
        if process_id is not None
        else int(_env_first(_ENV_PROCESS_ID) or 0)
    )
    coordinator_address = coordinator_address or _env_first(_ENV_COORDINATOR)

    # pre-flight host handshake: every peer must be reachable within the
    # timeout BEFORE we commit to the JAX rendezvous, and a dead peer
    # later turns into a CoordError instead of a hung collective.
    # Requires an explicit coordinator address: guessing 127.0.0.1 on a
    # pod launch that relies on jax.distributed auto-detection would
    # make every non-zero rank dial its own localhost and hang.
    want_host_coord = os.environ.get("HYPERION_HOST_COORD", "1") != "0"
    if _HOST_COORD is None and want_host_coord and coordinator_address:
        from hyperion_tpu.runtime.native_coord import DEFAULT_PORT, HostCoordinator

        host = coordinator_address.split(":")[0]
        port = int(os.environ.get("HYPERION_COORD_PORT", DEFAULT_PORT))
        _HOST_COORD = HostCoordinator(
            rank=process_id, world=num_processes, host=host, port=port,
            timeout_s=init_timeout_s,
        )
        _HOST_RANK = process_id
        log.info("host coordinator up (rank %d/%d via %s)",
                 process_id, num_processes, host)
    elif want_host_coord and not coordinator_address:
        log.info("no coordinator address configured; host-coordination "
                 "layer disabled (jax.distributed auto-detection launch)")

    if os.environ.get("HYPERION_SKIP_JAX_INIT") == "1":
        _HOST_RANK = process_id
        _JAX_SKIPPED = True
        _INITIALIZED = True
        return

    if coordinator_address and ":" not in coordinator_address:
        coordinator_address = f"{coordinator_address}:{DEFAULT_COORD_PORT}"
    log.info(
        "jax.distributed.initialize coord=%s procs=%d id=%d",
        coordinator_address, num_processes, process_id,
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=init_timeout_s,
    )
    _INITIALIZED = True


def cleanup() -> None:
    """Tear down the runtime (reference `cleanup()`: barrier + destroy PG,
    distributed_utils.py:122-125). Barrier first so no process exits while
    a peer still has collectives in flight."""
    global _INITIALIZED, _HOST_COORD, _HOST_RANK, _JAX_SKIPPED
    try:
        if _INITIALIZED:
            barrier("cleanup")
    finally:
        # teardown must happen even when the barrier raises (dead peer):
        # otherwise _INITIALIZED stays True, a later setup() no-ops on
        # stale state, and rank 0's listening socket blocks a rebind
        if _INITIALIZED and not _JAX_SKIPPED and jax.process_count() > 1:
            jax.distributed.shutdown()
        _INITIALIZED = False
        _JAX_SKIPPED = False
        if _HOST_COORD is not None:
            _HOST_COORD.close()
            _HOST_COORD = None
        _HOST_RANK = None  # also set in skip-jax mode without a coordinator


def _backends_ready() -> bool:
    # private, but the one installed JAX (pyproject.toml pins the line)
    # has it, and there is no public way to ask without initializing
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _single_process() -> bool:
    # Rank/count short-circuit, in three layers:
    #   1. jax.distributed ran (through setup()): jax is authoritative.
    #   2. The backend is already up: asking jax is free AND correct —
    #      on a TPU pod slice libtpu knows the true host index even
    #      without env vars, so the fall-through must win there.
    #   3. Backend not yet initialized and the launch env declares one
    #      process: the rank is 0 by construction. Asking jax here
    #      would *initialize* the backend — and so take the chip, which
    #      belongs to one process at a time: a `--supervise` parent
    #      asking for its rank would starve the child it is about to
    #      start — for an answer that is already known.
    if _INITIALIZED or int(_env_first(_ENV_NUM_PROCESSES) or 1) > 1:
        return False
    # libtpu pod-worker env (set by Cloud TPU on every pod host) is
    # multi-process evidence even with no RANK/WORLD_SIZE configured —
    # there the backend must be consulted for the true host index
    if any(os.environ.get(v) for v in
           ("TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "MEGASCALE_SLICE_ID")):
        return False
    return not _backends_ready()


def process_index() -> int:
    if _HOST_RANK is not None:
        # host-coordination-only mode (pre-flight/tests): answering from
        # the coordinator avoids initializing the backend — the whole
        # point is to run before chips are touched
        return _HOST_RANK
    if _single_process():
        return 0
    return jax.process_index()


def process_count() -> int:
    if _HOST_RANK is not None and _JAX_SKIPPED:
        # setup()'s resolved value (arguments win over env — rank and
        # world size must come from the same source)
        return _NUM_PROCESSES or int(_env_first(_ENV_NUM_PROCESSES) or 1)
    if _single_process():
        return 1
    return jax.process_count()


def is_primary() -> bool:
    """True on the process that owns logging/checkpoint duties — the
    'rank 0' of the reference's rank-0-only CSV/checkpoint pattern."""
    return process_index() == 0


def host_barrier(name: str = "host", timeout_s: float = 60.0) -> None:
    """Named host-level barrier through the C++ coordinator — no device
    work involved, so it is safe around checkpoint/file IO (the
    reference's `dist.barrier()` placement, distributed_utils.py:369,405)
    and it FAILS (CoordError) rather than hangs when a peer has died."""
    if _HOST_COORD is not None:
        log.debug("host_barrier %s", name)
        _HOST_COORD.barrier(timeout_s)


def peers_alive() -> int:
    """Coordinator's count of live hosts; process_count() when the host
    layer is off (single process or disabled)."""
    if _HOST_COORD is not None:
        return _HOST_COORD.alive_count()
    return jax.process_count()


def barrier(name: str = "barrier") -> None:
    """Cross-process sync point (reference: dist.barrier(),
    distributed_utils.py:369,405). On a single process this is a
    device-flush, which preserves the 'everything before me finished'
    meaning for timing code. Multi-process: host-level barrier first
    (fail-fast on dead peers), then the device-level sync. In
    host-coordination-only mode no backend is ever initialized."""
    host_barrier(name)
    if _JAX_SKIPPED:
        return
    if jax.process_count() == 1:
        jax.effects_barrier()
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
