#!/usr/bin/env bash
# Launch recipe for the FSDP language-model training job — the C12
# equivalent of the reference's `02_development/run_language_fsdp.sh`
# (env knobs + a pinned multi-device launch, reference lines 8-23).
#
# TPU translation of each knob class:
#   NCCL/RCCL env tuning  -> nothing: ICI collectives are compiled by
#                            XLA; there is no collnet/P2P switchboard.
#                            The knobs that DO exist are kept below.
#   torchrun --standalone -> single process drives every local chip via
#                            the mesh; no per-device process spawn.
#   multi-node torchrun   -> one process per HOST with the coordinator
#                            env (see MULTI-HOST below), not per chip.
set -euo pipefail

# ── single-host tuning ────────────────────────────────────────────────
# Compile-cache: placed by the program (utils/compile_cache.py) —
# JAX_COMPILATION_CACHE_DIR if you set it, else <repo>/.jax_cache; the
# scaling sweep's subprocesses inherit the variable.
# Don't let a long FSDP gather trip the coordinator heartbeat — the
# reference raised its watchdog to 7200 s for the same reason.
export JAX_DISTRIBUTED_HEARTBEAT_TIMEOUT_SECONDS="${JAX_DISTRIBUTED_HEARTBEAT_TIMEOUT_SECONDS:-300}"

EPOCHS="${EPOCHS:-25}"            # reference trains 25 epochs
BATCH="${BATCH:-32}"

# ── MULTI-HOST (optional) ─────────────────────────────────────────────
# Set these on every host; the framework reads them in runtime/dist.py:
#   WORLD_SIZE   number of host processes      (reference: RANK/WORLD_SIZE
#   RANK         this host's index 0..N-1       from torchrun, SURVEY C1)
#   MASTER_ADDR  host 0's address — serves both the JAX coordinator
#                (port 29500) and the C++ host coordinator (port 29501,
#                override with HYPERION_COORD_PORT)
# Pre-flight the host layer before committing chips (test_nccl.py role):
#   python -m hyperion_tpu.runtime.comm_check --host-only
if [[ "${WORLD_SIZE:-1}" -gt 1 ]]; then
  : "${RANK:?multi-host launch needs RANK}"
  : "${MASTER_ADDR:?multi-host launch needs MASTER_ADDR}"
  echo "[run_language_fsdp] host ${RANK}/${WORLD_SIZE} via ${MASTER_ADDR}"
  python -m hyperion_tpu.runtime.comm_check --host-only
fi

# comm sanity check on the real devices (README-prescribed test_nccl
# habit), then the job itself.
python -m hyperion_tpu.runtime.comm_check

exec python -m hyperion_tpu.cli.main \
  --model language_fsdp \
  --epochs "${EPOCHS}" \
  --batch_size "${BATCH}" \
  --precision bf16 \
  "$@"
