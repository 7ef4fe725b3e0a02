"""Restart supervisor — the reaction half of the run-health loop.

PR 2 built the diagnosis (`obs doctor` classifies a dead run from its
own telemetry); this closes the loop: `hyperion train --supervise
--max-restarts N` reruns the trainer as a subprocess and, on a nonzero
exit, asks the doctor what happened before deciding how to come back:

    crashed / hung / stalled  -> restart with exponential backoff (the
                                 verified-checkpoint walk-back resumes
                                 from the newest committed step)
    preempted (exit 75)       -> restart immediately-ish: the capacity
                                 event is over, the mid-epoch
                                 checkpoint is waiting
    diverged (exit 4, or the  -> quarantine the newest checkpoint
    doctor says so)              (`step_X.corrupt`) first, so the
                                 restart resumes from the PRIOR
                                 verified step instead of re-diverging
                                 from the same poisoned-adjacent state
    usage error (exit 2)      -> give up now: argparse rejections don't
                                 heal with retries

Each child runs with `HYPERION_ATTEMPT=<k>`; the trainers stamp that
into their `train_start` trace event and every heartbeat, so `obs
doctor` reports the restart lineage of the whole run directory.

Exit codes (the contract watching scripts defer to):
    0  the (possibly restarted) run finished
    3  gave up: max restarts exhausted — re-firing from outside would
       just burn the same wall; a human should look
    2  usage error passed through

The supervisor itself never touches a device backend — no
`dist`/`jax.devices()`/`process_index()` calls, and the checkpoint
package resolves its orbax half lazily — so it stays alive and
responsive when the child is wedged inside a dead backend.
"""

from __future__ import annotations

import time
from pathlib import Path

# The restart loop itself (attempt stamping, backoff, budget, give-up)
# is the shared core `hyperion_tpu/supervisor.py` — the serve
# supervisor (serve/server.py) runs the same loop with its own policy.
# This module keeps the TRAINING policy: doctor triage, divergence
# quarantine, and the free-restart rule for progressing preemptions.
from hyperion_tpu.supervisor import (  # noqa: F401 — re-exported API
    ATTEMPT_ENV,
    EXIT_GAVE_UP,
    EXIT_HEALTH_ABORT,
    EXIT_OK,
    EXIT_PREEMPTED,
    EXIT_USAGE,
    Decision,
    run_child as _run_child,
    supervise_loop,
)


def _consult_doctor(base_dir: str | Path,
                    prefer_diverged: bool = False) -> dict | None:
    """Diagnose the run dir's telemetry; None when there is nothing to
    read (e.g. --no-telemetry) — the caller falls back to exit-code-only
    triage. `prefer_diverged`: a `--model all` child that health-aborts
    on an early job still runs its REMAINING jobs, so the stream's last
    run (the doctor's default pick) can be a healthy later job — walk
    the runs newest-first for the one that actually diverged, so the
    quarantine hits the right job's checkpoint."""
    try:
        from hyperion_tpu.obs.doctor import diagnose, read_stream

        tele = Path(base_dir) / "telemetry.jsonl"
        if not tele.exists():
            return None
        d = diagnose(base_dir)
        if d.get("verdict") == "empty":
            return None
        if prefer_diverged and d.get("verdict") != "diverged":
            records, _, _ = read_stream(tele)
            run_ids: dict[str, None] = {}
            for r in records:
                if r.get("run"):
                    run_ids.setdefault(r["run"], None)
            for run in reversed(list(run_ids)[:-1]):
                alt = diagnose(base_dir, run=run)
                if alt.get("verdict") == "diverged":
                    return alt
        return d
    except Exception as e:  # noqa: BLE001 — triage is advisory
        print(f"[supervisor] doctor consult failed: {e}")
        return None


def _quarantine_newest(base_dir: str | Path, reason: str,
                       run: str | None = None) -> Path | None:
    """Quarantine the newest checkpoint of the DIVERGED job so the
    restart's walk-back resumes from its prior verified step. `run` is
    the doctor's run id (`{job}_{n}gpus_{ts}`): a `--model all` lineage
    has several job dirs under `<base_dir>/checkpoints/`, and step
    numbers are not comparable across jobs — quarantining a global max
    could sacrifice a healthy job's checkpoint while the diverged one
    kept its own. When the job can't be inferred, fall back to the
    most recently WRITTEN step dir (the diverged job is the one that
    was just training)."""
    import re

    from hyperion_tpu.checkpoint import integrity

    job = None
    if run and (m := re.match(r"^(.+)_\d+gpus_\d", str(run))):
        job = m.group(1)
    step_re = re.compile(r"^step_(\d+)$")
    root = Path(base_dir) / "checkpoints"
    candidates: list[tuple[int, Path]] = []  # (step, path) within a job
    fallback: list[tuple[float, int, Path]] = []
    if root.is_dir():
        for job_dir in root.iterdir():
            if not job_dir.is_dir():
                continue
            for p in job_dir.iterdir():
                if (m := step_re.match(p.name)) and p.is_dir():
                    if job and job_dir.name.startswith(job):
                        candidates.append((int(m.group(1)), p))
                    fallback.append(
                        (p.stat().st_mtime, int(m.group(1)), p))
    if candidates:
        _, newest = max(candidates)
    elif fallback:
        _, _, newest = max(fallback)
    else:
        return None
    # primary=True: the supervisor is the only process alive here, and
    # asking `dist` for rank would call into jax — whose backend init
    # can block forever exactly when a wedged child holds the TPU
    return integrity.quarantine(newest, reason, primary=True)


def supervise(
    child_argv: list[str],
    *,
    base_dir: str | Path,
    max_restarts: int = 2,
    backoff_s: float = 1.0,
    max_backoff_s: float = 30.0,
    run_child=_run_child,
    sleep=time.sleep,
) -> int:
    """Run `child_argv` under restart supervision. `run_child`/`sleep`
    are injectable for tests."""
    prev_step: list[int | None] = [None]  # closure cell for progress

    def decide(rc: int) -> Decision:
        diag = _consult_doctor(base_dir,
                               prefer_diverged=rc == EXIT_HEALTH_ABORT)
        verdict = diag.get("verdict") if diag else None
        diverged = rc == EXIT_HEALTH_ABORT or verdict == "diverged"
        print(f"[supervisor] child exit {rc}; doctor verdict: "
              f"{verdict or 'unavailable'}"
              + (f" ({diag.get('reason')})" if diag else ""))
        # Clean preemptions that made forward progress are free: on the
        # preemptible capacity this system targets, N capacity events
        # over a long run are normal life, not N failures — counting
        # them against --max-restarts would strand a healthy resumable
        # run. Progress is judged from the doctor's last_step, so a
        # child that exits 75 without advancing (a preemption loop, or
        # no telemetry to prove progress) still burns budget.
        cur_step = diag.get("last_step") if diag else None
        progressed = (cur_step is not None
                      and (prev_step[0] is None or cur_step > prev_step[0]))
        prev_step[0] = cur_step if cur_step is not None else prev_step[0]

        if diverged:
            # quarantine even when about to give up: whoever reruns by
            # hand (the exit-3 triage path) must not resume from the
            # same poisoned-adjacent checkpoint and re-diverge
            q = _quarantine_newest(
                base_dir,
                f"supervisor: diverged (child exit {rc}, verdict "
                f"{verdict or 'n/a'}); restarting from the prior "
                "verified step",
                run=diag.get("run") if diag else None,
            )
            print(f"[supervisor] diverged: quarantined "
                  f"{q.name if q else 'nothing (no checkpoints yet)'}")

        # immediate: the capacity event is over; the checkpoint waits
        return Decision.restart(
            free=rc == EXIT_PREEMPTED and progressed,
            immediate=rc == EXIT_PREEMPTED,
        )

    return supervise_loop(
        child_argv, decide=decide, max_restarts=max_restarts,
        backoff_s=backoff_s, max_backoff_s=max_backoff_s,
        run_child=run_child, sleep=sleep,
    )
