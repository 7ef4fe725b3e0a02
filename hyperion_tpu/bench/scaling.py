"""Scaling experiment driver — C10 (`run_scaling_experiment`).

Reference: `distributed_utils.py:780-831` shells out to
`torchrun --nproc_per_node=N run_distributed.py` per GPU count, then
runs the scaling report. The TPU shape: one process drives any number of
chips, so "N devices" is a *mesh size*, not a process count — each run
is a subprocess of the CLI with `--devices N` (subprocess, not in-proc,
so every run gets a fresh XLA client and clean HBM, and one failed count
doesn't kill the sweep, matching the reference's CalledProcessError
tolerance at :826-827).

The parent never asks JAX anything: a chip belongs to one process at
a time, and a parent that had counted the devices would hold the chips
its children need. So the device counts come from the caller
(`--scaling_devices`), and the simulated CPU backend
(`--xla_force_host_platform_device_count` — the collectives and
sharding are real, the absolute times are not; the report is labeled
accordingly) is chosen by flag (`--simulate-cpu`), not detected.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

from hyperion_tpu.metrics.scaling_report import create_scaling_report


SIMULATED_COUNTS = (1, 2, 4, 8)  # the default sweep of a simulated run

# The reference's sweep covers all four trainers (distributed_utils.py:
# 628-650 infers the job from the run-id filename); llama scales in its
# LoRA form and at the tiny (architecture-true) size so the simulated CPU
# mesh can actually run it.
SCALING_JOBS = ("language_ddp", "cifar", "language_fsdp", "llama")
_JOB_EXTRA_FLAGS = {"llama": ("--llama_size", "tiny", "--lora")}


def run_scaling_experiment(
    device_counts: list[int] | None = None,
    models: str | list[str] = SCALING_JOBS,
    epochs: int = 3,
    base_dir: str = "data",
    steps_per_epoch: int = 20,
    simulate_on_cpu: bool = False,
    batch_size: int | None = None,
    validate: bool = True,
) -> list[dict]:
    """Run each job at each device count in a fresh subprocess; report."""
    if not device_counts:
        if not simulate_on_cpu:
            raise ValueError(
                "a sweep on real devices needs its device counts "
                "(--scaling_devices): the parent does not ask JAX, which "
                "would take the chips its children need")
        device_counts = list(SIMULATED_COUNTS)
    jobs = [models] if isinstance(models, str) else list(models)

    for model in jobs:
        for n in device_counts:
            cmd = [
                sys.executable, "-m", "hyperion_tpu.cli.main",
                "--model", model, "--epochs", str(epochs),
                "--base_dir", base_dir, "--devices", str(n),
                "--steps-per-epoch", str(steps_per_epoch),
                *_JOB_EXTRA_FLAGS.get(model, ()),
            ]
            if batch_size:
                cmd += ["--batch_size", str(batch_size)]
            if not validate:
                cmd += ["--no-validate"]
            env = dict(os.environ)
            if simulate_on_cpu:
                env["JAX_PLATFORMS"] = "cpu"
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count="
                    + str(max(device_counts))
                )
            label = "simulated-cpu" if simulate_on_cpu else "default backend"
            print(f"[scaling] {model} x{n} ({label}): {' '.join(cmd[2:])}")
            try:
                subprocess.run(cmd, check=True, env=env)
            except subprocess.CalledProcessError as e:
                # one failed count must not kill the sweep (reference :826-827)
                print(f"[scaling] {model} with {n} device(s) failed: {e}")
            time.sleep(2)  # settle, as the reference did (:823)

    return create_scaling_report(f"{base_dir}/distributed")
