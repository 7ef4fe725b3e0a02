"""Config system — one real, code-driving configuration surface.

The reference has three uncoordinated mechanisms (SURVEY §5.6): argparse
flags, env vars, and `Phase 1/default_config.json` — a full schema that
*no code ever loads* (C23). This module keeps the reference's JSON schema
shape (hardware / optimization / benchmarking / distributed blocks) but
wires it into every trainer and benchmark, and adds the train-loop
hyperparameters the reference hardcoded in function bodies
(`distributed_utils.py:152,161,226,231,334,450,470,503`).
"""

from __future__ import annotations

import dataclasses
import json
import typing
from pathlib import Path
from typing import Any

from hyperion_tpu.runtime.mesh import MeshSpec


def _from_dict(cls, d: dict):
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in d.items():
        if k not in names:
            continue  # forward/back compat: ignore unknown keys
        t = hints.get(k)
        if dataclasses.is_dataclass(t) and isinstance(v, dict):
            v = _from_dict(t, v)
        elif t is tuple and isinstance(v, list):
            v = tuple(v)  # JSON arrays come back as lists; keep tuple fields tuples
        kwargs[k] = v
    return cls(**kwargs)


@dataclasses.dataclass
class HardwareConfig:
    platform: str = "tpu"
    chips_expected: int = 0  # 0 = whatever jax.devices() reports
    hbm_gb_per_chip: float = 16.0  # v5e


@dataclasses.dataclass
class OptimizationConfig:
    precision: str = "bf16"          # fp32 | bf16 | bf16_full (precision.policy)
    remat: str = "none"              # none | full | dots | dots_no_batch
    grad_accum_steps: int = 1
    grad_clip_norm: float = 0.0      # 0 disables (FSDP loops use 1.0)
    compile_tier: str = "jit"        # jit | jit+pallas (compile_bench variants)
    attention_impl: str | None = None  # override just attention: xla | pallas
    donate_state: bool = True        # buffer donation into the train step


@dataclasses.dataclass
class DistributedConfig:
    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1                    # pipeline stages (parallel.pipeline)
    pipe_microbatches: int = 0       # 0 = same as pipe (GPipe M >= S)
    expert: int = 1                  # expert-parallel shards (ops.moe)
    max_devices: int = 0  # 0 = all; >0 restricts the mesh to the first N
    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None

    def mesh_spec(self) -> MeshSpec:
        return MeshSpec(data=self.data, fsdp=self.fsdp, model=self.model,
                        seq=self.seq, pipe=self.pipe, expert=self.expert)


@dataclasses.dataclass
class TrainConfig:
    # reference hardcoded values, per trainer (distributed_utils.py):
    #   LM DDP: bs 32, lr 2e-4 (:152,161)  CIFAR: bs 64, lr 1e-3 (:226,231)
    #   LM FSDP: lr 1e-4 (:334)            Llama: bs 1, lr 1e-5 wd 0.01 (:450,503)
    model: str = "transformer_lm"
    epochs: int = 3
    batch_size: int = 32             # per-step GLOBAL batch
    learning_rate: float = 2e-4
    lr_schedule: str = "constant"    # constant | cosine | warmup_cosine
    warmup_steps: int = 0            # warmup_cosine's linear ramp length
    weight_decay: float = 0.0
    seq_len: int = 128               # reference tokenization window
    # which corpus split the LM trainers optimize on. The default is the
    # reference's layout; "test" exists because the reference snapshot
    # ships REAL WikiText-2 arrows only for validation/test (its train
    # arrow is absent — /root/reference/data/wikitext2_tokenized/train
    # holds metadata only), so real-data runs train on the real test
    # split (the larger: 2891 packed 128-token rows — 4358 is the
    # pre-filter count; data/wikitext2_tokenized/README.md) and
    # validate on the real val split.
    train_split: str = "train"
    steps_per_epoch: int = 0         # 0 = full pass; >0 caps steps (smoke/bench runs)
    validate: bool = True            # per-epoch val pass (exceeds reference)
    # input-pipeline overlap (data/prefetch.py): batches assembled this
    # many steps ahead on a background thread, so host fancy-indexing +
    # H2D transfer overlap device compute. Semantics-neutral (the
    # prefetched run is batch-for-batch identical to the sync path);
    # 0 = synchronous assembly on the critical path (the fallback
    # switch, still timed for the input_wait_s gauge). Depth beyond 2-3
    # only buys memory pressure: one worker can only assemble so far
    # ahead of a consumer that drains the queue every step.
    prefetch_depth: int = 2
    # checkpoint saves stream to disk in the background while training
    # continues (checkpoint/io.py wait_pending is the commit point: the
    # integrity manifest lands only after the write finishes, so a kill
    # mid-save can never yield a verified-but-partial dir). False =
    # every save blocks until committed, the pre-overlap behavior.
    async_checkpoint: bool = True
    # run telemetry (obs/): step spans + per-epoch metric snapshots to
    # <base_dir>/telemetry.jsonl (appended; primary process only). Reports
    # via `hyperion obs summarize`. HYPERION_TELEMETRY=0/path overrides.
    telemetry: bool = True
    # flight recorder (obs/heartbeat.py): rewrite <base_dir>/heartbeat.json
    # every N steps (and at phase changes) so `obs doctor` and the stage
    # watcher can tell hung from slow. Rides the telemetry switch; 0
    # disables the step cadence (phase transitions still pulse).
    heartbeat_every: int = 25
    # in-band anomaly policy (obs/health.py): what a FATAL anomaly
    # (non-finite loss/grads) does to the run. off = no monitoring;
    # warn = print + trace event; checkpoint = also save a tagged
    # checkpoint; abort = stop the run (exports skipped, like preemption)
    health_policy: str = "warn"
    # deterministic fault-injection plan (testing/chaos.py): e.g.
    # "kill@step=6,corrupt_ckpt@latest". Empty = HYPERION_CHAOS env,
    # else off. Step faults fire once per run lineage (fire record in
    # <base_dir>/chaos_state.json survives supervisor restarts).
    chaos: str = ""
    profile_dir: str = ""            # jax.profiler trace of epoch 1 (off when empty)
    seed: int = 0
    base_dir: str = "data"
    # corpus location override. base_dir doubles as the RUN OUTPUT root
    # (metrics/checkpoints land under it), so capture runs point it at
    # results/tpu_runs — which would also move the data search there.
    # data_dir breaks the tie: when set, datasets load from here while
    # outputs keep following base_dir. Empty = data under base_dir.
    data_dir: str = ""
    log_every: int = 50
    lora: bool = False
    lora_rank: int = 16              # reference LoraConfig r=16 α=32 (:470)
    lora_alpha: float = 32.0
    lora_dropout: float = 0.05
    # also export base+adapters merged (models/lora.py:merge_lora) next
    # to the adapters-only npz, so the generation CLI can load a LoRA
    # fine-tune directly. Off by default: gathering a 7B base to host
    # doubles export time/disk for runs that only need adapters.
    export_merged: bool = False
    moe_experts: int = 0             # >0: language jobs use the MoE LM
    moe_top_k: int = 2
    moe_every: int = 2               # every k-th block is sparse
    # plan-only mode: eval_shape the full TrainState (params/opt/sharding
    # specs) and print the byte-accounting memory plan WITHOUT touching a
    # device — validates e.g. the 7B config end-to-end on a CPU box
    dry_init: bool = False


@dataclasses.dataclass
class BenchmarkingConfig:
    batch_sizes: tuple = (1, 2, 4, 8, 16, 32, 64, 128)
    models: tuple = ("resnet50", "vit_b16", "custom_transformer")
    precisions: tuple = ("fp32", "bf16")
    iterations: int = 50
    warmup_iterations: int = 10


@dataclasses.dataclass
class Config:
    hardware: HardwareConfig = dataclasses.field(default_factory=HardwareConfig)
    optimization: OptimizationConfig = dataclasses.field(default_factory=OptimizationConfig)
    distributed: DistributedConfig = dataclasses.field(default_factory=DistributedConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    benchmarking: BenchmarkingConfig = dataclasses.field(default_factory=BenchmarkingConfig)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def save(self, path: str | Path) -> None:
        Path(path).write_text(json.dumps(self.to_dict(), indent=2, default=list))

    @classmethod
    def load(cls, path: str | Path) -> "Config":
        return cls.from_dict(json.loads(Path(path).read_text()))

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        return _from_dict(cls, d)

    def override(self, **kv) -> "Config":
        """dotted-path overrides: cfg.override(**{"train.epochs": 5})."""
        cfg = Config.from_dict(self.to_dict())
        for key, val in kv.items():
            obj = cfg
            *parents, leaf = key.split(".")
            for p in parents:
                obj = getattr(obj, p)
            if not hasattr(obj, leaf):
                raise AttributeError(f"no config field {key!r}")
            setattr(obj, leaf, val)
        return cfg


def default_config() -> Config:
    return Config()
