"""CLI launcher — C11 (`run_distributed.py`), same surface, TPU-native.

Reference CLI (`02_development/run_distributed.py:38-67`):
  --model {language_ddp,cifar,language_fsdp,llama,all,scaling}
  --epochs --base_dir --hf_token --model_id --lora --batch_size
  --progress_every --scaling_gpus
launched under torchrun per GPU process. Here there is no torchrun:
one process per host drives every local chip through the mesh; multi-host
runs bootstrap via `hyperion_tpu.runtime.dist.setup()` env vars
(JAX_COORDINATOR_ADDRESS / RANK-style compatibility, dist.py).

Differences owned: --hf_token is gone (zero-egress; local checkpoints
only), --progress_every is replaced by per-epoch logging plus
--steps-per-epoch, and mesh/precision knobs are exposed because the
framework actually has them (reference hardcoded those — SURVEY §5.6).

Every run ends with `create_scaling_report` on the primary process, as
the reference's launcher did (run_distributed.py:148-149).
"""

from __future__ import annotations

import argparse
import sys

from hyperion_tpu.config import Config
from hyperion_tpu.metrics.scaling_report import create_scaling_report
from hyperion_tpu.runtime import dist
from hyperion_tpu.utils.compile_cache import place_compile_cache

MODELS = ("language_ddp", "cifar", "language_fsdp", "llama", "all", "scaling")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hyperion_tpu", description=__doc__.splitlines()[0]
    )
    p.add_argument("--model", choices=MODELS, default="language_ddp")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--base_dir", default="data")
    p.add_argument("--data_dir", default="",
                   help="load corpora from here instead of base_dir "
                        "(base_dir stays the run-output root — capture "
                        "runs use --base_dir results/tpu_runs --data_dir "
                        "data to train on the committed real arrows)")
    p.add_argument("--batch_size", type=int, default=None,
                   help="global batch (defaults per job: LM 32, CIFAR 64, llama 8)")
    p.add_argument("--lora", action="store_true",
                   help="llama: LoRA adapters instead of FSDP full fine-tune")
    p.add_argument("--export-merged", action="store_true",
                   help="LoRA runs: also export base+adapters merged so "
                        "infer.generate can load the fine-tune directly")
    p.add_argument("--llama_size", choices=["tiny", "7b", "70b"], default="7b")
    p.add_argument("--steps-per-epoch", type=int, default=0,
                   help="cap steps per epoch (0 = full pass)")
    p.add_argument("--seq_len", type=int, default=0,
                   help="token window for LM jobs (0 = the reference's "
                        "128); smoke/chaos runs shrink it")
    p.add_argument("--precision", choices=["fp32", "bf16", "bf16_full"],
                   default="bf16")
    p.add_argument("--mesh", default=None,
                   help="axis sizes data,fsdp,model,seq[,pipe[,expert]] "
                        "(e.g. 2,4,1,1 or 2,1,1,1,4); default: all-data, "
                        "or all-fsdp for *_fsdp jobs")
    p.add_argument("--pipe_microbatches", type=int, default=0,
                   help="GPipe microbatches when the mesh has a pipe "
                        "axis (0 = one per stage)")
    p.add_argument("--moe_experts", type=int, default=0,
                   help="language jobs: >0 swaps in the MoE LM with this "
                        "many experts (shard them with --mesh's expert "
                        "axis)")
    p.add_argument("--moe_top_k", type=int, default=2)
    p.add_argument("--devices", type=int, default=0,
                   help="restrict to first N devices (scaling runs)")
    p.add_argument("--scaling_devices", type=int, nargs="*", default=None,
                   help="device counts for --model scaling (required "
                        "on real devices; --simulate-cpu defaults to "
                        "1 2 4 8)")
    p.add_argument("--scaling_jobs", nargs="*", default=None,
                   help="jobs for --model scaling (default: all four "
                        "reference jobs — language_ddp cifar language_fsdp "
                        "llama)")
    p.add_argument("--simulate-cpu", action="store_true",
                   help="scaling: run the sweep's children on the "
                        "CPU-simulated mesh (the parent never asks JAX "
                        "for devices; without this flag the children "
                        "use the default backend)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dry-init", action="store_true",
                   help="plan-only: eval_shape the TrainState and print "
                        "the memory plan (global/per-device bytes, param "
                        "count) without touching a device — sanity-check "
                        "a 7B config on any box")
    p.add_argument("--no-validate", action="store_true",
                   help="skip the per-epoch validation pass")
    p.add_argument("--no-telemetry", action="store_true",
                   help="skip the run-telemetry JSONL stream "
                        "(<base_dir>/telemetry.jsonl; see `hyperion_tpu "
                        "obs summarize`) AND the heartbeat flight "
                        "recorder that rides it")
    p.add_argument("--heartbeat-every", type=int, default=25,
                   help="rewrite <base_dir>/heartbeat.json every N steps "
                        "so `obs doctor` / the stage watcher can tell "
                        "hung from slow (0 = phase transitions only)")
    p.add_argument("--health-policy", default="warn",
                   choices=["off", "warn", "checkpoint", "abort"],
                   help="in-band anomaly escalation (obs/health.py). "
                        "warn logs `health` events; checkpoint also "
                        "saves evidence on STATISTICAL anomalies "
                        "(spikes/explosions — non-finite trees are "
                        "never saved: they are poisoned); abort stops "
                        "the run on non-finite loss/grads like a "
                        "preemption (exports skipped) — the only "
                        "policy that prevents a diverged final export")
    p.add_argument("--profile-dir", default="",
                   help="capture a jax.profiler trace of the first epoch "
                        "into this directory (read it with `obs profile "
                        "--summarize <dir>`)")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="assemble batches this many steps ahead on a "
                        "background thread so host input work overlaps "
                        "device compute (semantics-neutral — identical "
                        "batches in identical order; 0 = synchronous "
                        "assembly on the critical path)")
    p.add_argument("--no-async-checkpoint", action="store_true",
                   help="make every checkpoint save block until the "
                        "bytes are committed (default: saves stream out "
                        "in the background while training continues; "
                        "the integrity manifest is only written after "
                        "the write finishes)")
    p.add_argument("--chaos", default="",
                   help="deterministic fault plan (testing/chaos.py): "
                        "comma-separated kill@step=N, sigterm@step=N, "
                        "nan_loss@step=N, stall@step=N:SECS, "
                        "corrupt_ckpt@latest, io_fail@p=X; step faults "
                        "fire once per run lineage")
    p.add_argument("--supervise", action="store_true",
                   help="run the trainer as a supervised subprocess: on "
                        "nonzero exit consult `obs doctor` — crashed/"
                        "hung/preempted restart with backoff (resuming "
                        "from the newest verified checkpoint), diverged "
                        "quarantines the newest checkpoint first "
                        "(train/supervisor.py)")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="--supervise: restarts before giving up with "
                        "exit 3")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine", "warmup_cosine"],
                   help="LR decay over the run (beyond the reference's "
                        "fixed LR); schedules are step-functions inside "
                        "the jitted update")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--grad_accum", type=int, default=1)
    p.add_argument("--remat",
                   choices=["none", "full", "dots", "dots_no_batch"],
                   default=None,
                   help="activation-remat policy (precision.remat): full = "
                        "recompute everything; dots keeps matmul outputs "
                        "(default: full for llama, none otherwise)")
    p.add_argument("--compile-tier", choices=["jit", "jit+pallas"],
                   default="jit",
                   help="jit+pallas swaps in the in-tree flash-attention "
                        "and fused-norm kernels (max-autotune analogue)")
    p.add_argument("--attention-impl",
                   choices=["xla", "pallas", "auto", "ring", "ulysses"],
                   default=None,
                   help="override just the attention kernel, leaving norms "
                        "on the tier default; auto = geometry-aware "
                        "pallas/xla crossover; ring/ulysses = sequence "
                        "parallelism over the mesh's seq axis")
    p.add_argument("--train-split", default="train",
                   help="corpus split LM jobs optimize on (default train). "
                        "'test' trains on the REAL WikiText-2 test arrow — "
                        "the largest real split the reference snapshot "
                        "ships (its train arrow is absent)")
    return p


_JOB_DEFAULTS = {
    # reference hardcoded hyperparameters per trainer (SURVEY §5.6):
    # bs 32 / lr 2e-4 LM-DDP; bs 64 / lr 1e-3 CIFAR; lr 1e-4 LM-FSDP;
    # bs 1 / lr 1e-5 wd 0.01 llama (bs 8 here — a v5e fits it)
    "language_ddp": dict(batch_size=32, learning_rate=2e-4),
    "language_fsdp": dict(batch_size=32, learning_rate=1e-4),
    "cifar": dict(batch_size=64, learning_rate=1e-3),
    "llama": dict(batch_size=8, learning_rate=1e-5, weight_decay=0.01),
}


def make_config(args, job: str) -> Config:
    cfg = Config()
    d = _JOB_DEFAULTS[job]
    cfg.train.epochs = args.epochs
    cfg.train.base_dir = args.base_dir
    cfg.train.data_dir = args.data_dir
    cfg.train.batch_size = args.batch_size or d["batch_size"]
    cfg.train.learning_rate = args.lr or d["learning_rate"]
    cfg.train.lr_schedule = args.lr_schedule
    cfg.train.warmup_steps = args.warmup_steps
    cfg.train.weight_decay = d.get("weight_decay", 0.0)
    cfg.train.steps_per_epoch = args.steps_per_epoch
    if args.seq_len:
        cfg.train.seq_len = args.seq_len
    cfg.train.train_split = args.train_split
    cfg.train.chaos = args.chaos
    cfg.train.validate = not args.no_validate
    cfg.train.telemetry = not args.no_telemetry
    cfg.train.heartbeat_every = args.heartbeat_every
    cfg.train.health_policy = args.health_policy
    cfg.train.dry_init = args.dry_init
    cfg.train.profile_dir = args.profile_dir
    cfg.train.prefetch_depth = args.prefetch_depth
    cfg.train.async_checkpoint = not args.no_async_checkpoint
    cfg.train.seed = args.seed
    cfg.train.lora = args.lora
    cfg.train.export_merged = args.export_merged
    cfg.train.model = f"llama_{args.llama_size}" if job == "llama" else cfg.train.model
    cfg.optimization.precision = args.precision
    cfg.optimization.grad_accum_steps = args.grad_accum
    # 7B/70B llama don't fit un-rematerialized on one chip; tiny llama and
    # every other job default to no remat. An explicit --remat always wins.
    needs_remat = job == "llama" and args.llama_size in ("7b", "70b")
    cfg.optimization.remat = args.remat or ("full" if needs_remat else "none")
    cfg.optimization.compile_tier = args.compile_tier
    cfg.optimization.attention_impl = args.attention_impl
    if job in ("language_fsdp", "llama"):
        cfg.optimization.grad_clip_norm = 1.0  # reference clip 1.0 (:351,522)
    cfg.distributed.max_devices = args.devices
    cfg.distributed.pipe_microbatches = args.pipe_microbatches
    cfg.train.moe_experts = args.moe_experts
    cfg.train.moe_top_k = args.moe_top_k
    if args.mesh:
        sizes = [int(x) for x in args.mesh.split(",")]
        if len(sizes) not in (4, 5, 6):
            raise SystemExit(
                "--mesh wants data,fsdp,model,seq[,pipe[,expert]], got "
                f"{args.mesh!r}"
            )
        axes = ("data", "fsdp", "model", "seq", "pipe", "expert")
        for name, v in zip(axes, sizes):
            setattr(cfg.distributed, name, v)
    elif job in ("language_fsdp",) or (job == "llama" and not args.lora):
        cfg.distributed.data = 1
        cfg.distributed.fsdp = -1  # whole mesh on the fsdp axis
    return cfg


def run_job(args, job: str):
    from hyperion_tpu.train import trainer

    if job == "language_ddp":
        return trainer.train_language_model(make_config(args, job), "language_ddp")
    if job == "language_fsdp":
        return trainer.train_language_model(make_config(args, job), "language_fsdp")
    if job == "cifar":
        return trainer.train_cifar_model(make_config(args, job), "cifar_ddp")
    if job == "llama":
        return trainer.train_llama(make_config(args, job), "llama")
    raise ValueError(job)


def _strip_supervise_flags(argv: list[str]) -> list[str]:
    from hyperion_tpu.supervisor import strip_flags

    return strip_flags(argv, {"--supervise"}, {"--max-restarts"})


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "train":
        # `hyperion train --supervise ...` — explicit-subcommand alias
        # for the default training surface (obs already dispatches so)
        argv = argv[1:]
    if argv and argv[0] == "obs":
        # telemetry subcommands (`obs summarize <telemetry.jsonl>`,
        # `obs doctor <run dir>`, `obs diff <a> <b>`, `obs trace
        # <dir>`, `obs top <dir>` — the live fleet dashboard over the
        # exposition sockets) — pure file/socket tools, no devices
        # touched
        from hyperion_tpu.obs.report import main as obs_main

        return obs_main(argv[1:])
    if argv and argv[0] == "serve":
        # continuous-batching inference server (`hyperion serve --ckpt
        # ...` — serve/server.py owns its full arg surface)
        from hyperion_tpu.serve.server import main as serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "simulate":
        # fleet flight simulator (`hyperion simulate herd --replicas
        # 200` — serve/simulate.py plays a scenario over the real
        # routing/queueing policy code on a virtual clock; no devices,
        # no jax, no subprocesses)
        from hyperion_tpu.serve.simulate import main as sim_main

        return sim_main(argv[1:])
    if argv and argv[0] == "route":
        # replica-tier router (`hyperion route --replicas N --ckpt ...`
        # — serve/router.py owns its arg surface; the router process
        # never touches a jax backend, only its replica children do)
        from hyperion_tpu.serve.router import main as route_main

        return route_main(argv[1:])
    p = build_parser()
    args = p.parse_args(argv)
    if args.dry_init and args.model == "scaling":
        p.error("--dry-init plans a single job's TrainState; it does not "
                "apply to the scaling sweep (pick one of its jobs instead)")
    if args.supervise:
        # the supervisor stays jax-free and re-execs THIS command (minus
        # the supervision flags) as the child it watches
        from hyperion_tpu.train.supervisor import supervise

        child = [sys.executable, "-m", "hyperion_tpu.cli.main",
                 *_strip_supervise_flags(argv)]
        return supervise(child, base_dir=args.base_dir,
                         max_restarts=args.max_restarts)
    dist.setup()
    # before any compile: restarted/resumed runs reload the train-step
    # executable from the cache instead of recompiling it
    place_compile_cache()
    rc = 0

    if args.model == "scaling":
        from hyperion_tpu.bench.scaling import SCALING_JOBS, run_scaling_experiment

        run_scaling_experiment(
            device_counts=args.scaling_devices,
            models=args.scaling_jobs or SCALING_JOBS,
            epochs=args.epochs,
            base_dir=args.base_dir,
            steps_per_epoch=args.steps_per_epoch or 20,
            simulate_on_cpu=args.simulate_cpu,
            batch_size=args.batch_size,
            validate=not args.no_validate,
        )
    else:
        # lazy: `hyperion obs ...` must not pay the trainer import chain
        from hyperion_tpu.train.supervisor import (
            EXIT_HEALTH_ABORT,
            EXIT_PREEMPTED,
        )

        jobs = (
            ["language_ddp", "cifar", "language_fsdp", "llama"]
            if args.model == "all" else [args.model]
        )
        for job in jobs:  # reference 'all' runs the four jobs sequentially
            res = run_job(args, job)
            # exit codes the supervisor (and any watcher) branches on:
            # 4 = health policy aborted a diverged run (quarantine then
            # restart from the prior verified step); 75 = clean
            # preemption with a resumable checkpoint (EX_TEMPFAIL —
            # restart when capacity returns). A diverged verdict
            # outranks a preemption from an earlier job in --model all.
            if res.preempted == "health_abort":
                rc = EXIT_HEALTH_ABORT
            elif res.preempted and rc == 0:
                rc = EXIT_PREEMPTED

    # scaling already reported from inside run_scaling_experiment
    if args.model != "scaling" and dist.is_primary():
        create_scaling_report(f"{args.base_dir}/distributed")
    dist.cleanup()
    return rc


if __name__ == "__main__":
    sys.exit(main())
