"""Device-mesh construction for data / fsdp / model / seq parallelism.

TPU-native analogue of the reference's process-group runtime
(`02_development/distributed_utils.py:96-125` — `setup`/`_local_gpu`):
instead of one process per GPU with NCCL rank mapping, JAX runs one
process per host and sees every local chip; parallelism is expressed as a
`jax.sharding.Mesh` whose axes ride the ICI fabric (and DCN across
slices).  Collectives are inserted by XLA from sharding annotations, the
role RCCL plays in the reference.

Axes:
  data   pure data parallelism  (reference: DDP, distributed_utils.py:159)
  fsdp   parameter/grad/opt-state sharding (reference: FSDP FULL_SHARD,
         distributed_utils.py:328-332); also shards the batch
  model  tensor parallelism (absent in the reference — SURVEY §2.2 — but
         the axis is kept available by design)
  seq    sequence/context parallelism for ring attention (long-context
         headroom; absent in the reference, SURVEY §5.7)
  pipe   pipeline parallelism: stages hold stacked layer params and
         activations rotate stage→stage (parallel/pipeline.py; absent in
         the reference — SURVEY §2.2 PP row — built as TPU headroom)
  expert expert parallelism: MoE expert weights live one-expert-set per
         coordinate and token blocks all-to-all to them (ops/moe.py;
         absent in the reference — SURVEY §2.2 EP row)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class AxisName:
    DATA = "data"
    FSDP = "fsdp"
    MODEL = "model"
    SEQ = "seq"
    PIPE = "pipe"
    EXPERT = "expert"

    ALL = (DATA, FSDP, MODEL, SEQ, PIPE, EXPERT)
    # Batch is sharded over every data-like axis: the fsdp axis also
    # consumes batch (FSDP is data-parallel in its activation flow).
    BATCH = (DATA, FSDP)


# every axis in GSPMD/Auto mode (the explicit-sharding mode rejects ops
# whose output sharding is ambiguous)
_AUTO = (jax.sharding.AxisType.Auto,) * len(AxisName.ALL)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` on exactly one axis means "infer from
    the device count"; every other axis must divide it."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = dataclasses.asdict(self)
        infer = [k for k, v in sizes.items() if v == -1]
        if len(infer) > 1:
            raise ValueError(f"at most one axis may be -1, got {infer}")
        bad = {k: v for k, v in sizes.items() if v != -1 and v < 1}
        if bad:
            raise ValueError(f"axis sizes must be >= 1 (or -1 to infer): {bad}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if infer:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}"
                )
            sizes[infer[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} wants {fixed} devices, have {n_devices}")
        return MeshSpec(**sizes)

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.model, self.seq, self.pipe,
                self.expert)


def make_mesh(
    spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the global mesh. Defaults to all-data-parallel over every
    addressable device — the analogue of the reference's torchrun
    world with one DDP rank per GPU."""
    devices = list(devices) if devices is not None else jax.devices()
    spec = (spec or MeshSpec()).resolve(len(devices))
    # Auto axis types = GSPMD mode: shardings are layout hints and XLA's
    # partitioner resolves every op + inserts collectives (jax 0.9 defaults
    # make_mesh to Explicit, the sharding-in-types mode, which instead
    # rejects ops whose output sharding is ambiguous — e.g. embedding
    # gathers of a batch-sharded index into an fsdp-sharded table).
    # jax.make_mesh picks a device order that keeps adjacent mesh
    # coordinates ICI-adjacent where it can; fall back to reshape for
    # explicit device lists.
    if devices == jax.devices():
        return jax.make_mesh(spec.shape, AxisName.ALL, axis_types=_AUTO)
    arr = np.asarray(devices).reshape(spec.shape)
    return Mesh(arr, AxisName.ALL, axis_types=_AUTO)


def make_abstract_mesh(spec: MeshSpec) -> jax.sharding.AbstractMesh:
    """Shape-only mesh for planning (`--dry-init`): no devices are
    touched — `jax.devices()` is never called, so it works with a dead
    backend — and axis sizes may exceed the local device count (plan a
    64-chip pod layout from a laptop). Every axis must be explicit:
    there is no device count to infer ``-1`` from."""
    if -1 in spec.shape:
        raise ValueError(
            f"abstract mesh needs explicit axis sizes (no -1): {spec}"
        )
    return jax.sharding.AbstractMesh(spec.shape, AxisName.ALL,
                                     axis_types=_AUTO)


# --- active mesh -------------------------------------------------------
# Model code is deliberately mesh-agnostic, but the sequence-parallel
# attention impls (ring/ulysses) are shard_maps that need the Mesh
# object. The TRAINING mesh is registered explicitly (trainers do it
# right after building theirs; make_mesh deliberately does not — a bench
# sweep building a side mesh must never silently rebind a live model's
# attention); ops.attention reads it when impl is "ring"/"ulysses" so a
# model config string is enough to turn on sequence parallelism.

_ACTIVE_MESH: Mesh | None = None


def set_active_mesh(mesh: Mesh | None) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH


class activate_mesh:
    """Scoped registration: `with activate_mesh(mesh): ...` restores the
    previous active mesh on exit (what tests and nested runs want)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        self.prev = active_mesh()
        set_active_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_active_mesh(self.prev)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [batch, ...] array: batch split over (data, fsdp);
    trailing dims replicated (PartitionSpec leaves them unlisted).

    The analogue of `DistributedSampler` handing each rank a disjoint
    shard (distributed_utils.py:151) — except here a single global array
    is laid out across devices and XLA keeps every computation local to
    its shard.
    """
    return NamedSharding(mesh, P(AxisName.BATCH))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def global_batch_size(per_device: int, mesh: Mesh) -> int:
    n = mesh.shape[AxisName.DATA] * mesh.shape[AxisName.FSDP]
    return per_device * n
