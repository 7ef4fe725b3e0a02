"""Checkpointing: sharded save/restore + gathered export + verified resume.

Reference (SURVEY §5.4): save-only, end-of-run. DDP does a rank-0
`torch.save(model.module.state_dict())` (`distributed_utils.py:195-199`);
FSDP gathers FULL_STATE_DICT to rank-0 CPU with a SHARDED_STATE_DICT
fallback (`:374-405`). There is NO resume path anywhere in the reference.

TPU-native shape, exceeding that:
  * `save` / `restore`   — orbax sharded checkpoints: every host writes
    its own shards (the SHARDED_STATE_DICT analogue, but the *primary*
    path, not the fallback — gathering a sharded model to one host is the
    thing that OOMs, as the reference's try/except tacitly admits).
    Restore takes a sharding tree, so a checkpoint written on one mesh
    reshards onto another.
  * **async saves**      — `save(..., wait=False)` returns as soon as
    the device arrays are snapshotted to host (orbax's async dispatch);
    the disk write streams out on a background thread while training
    continues. `wait_pending()` is the commit point: it blocks on
    `wait_until_finished()` and only THEN writes the integrity
    manifest, so an interrupted async save is indistinguishable from
    any other uncommitted dir (orbax stages into a
    `*.orbax-checkpoint-tmp-*` dir that the `step_*` regex never
    matches; a kill mid-write leaves no resume candidate at all, and a
    kill after orbax's rename but before the manifest leaves an
    unverified dir the walk-back arbitrates via orbax's own commit
    marker). At most ONE save is in flight: a new `save` (and
    `restore`) finalizes the previous one first, and every trainer
    exit path drains via `wait_pending` before exporting.
  * **verified resume**  — `save` commits a `manifest.json` (file list,
    sizes, checksums, step, mesh shape, kernel rev —
    `checkpoint/integrity.py`) after the orbax write returns; `restore`
    walks back from the newest step to the newest *verified* one,
    quarantining failures as `step_X.corrupt` instead of bricking every
    future resume on one partial dir.
  * **retry/backoff**    — checkpoint IO routes through
    `utils.retry.retry_call`: transient storage faults (the only kind a
    preemptible fleet sees at scale) back off and retry; permanent ones
    surface to the walk-back.
  * `export_gathered`    — full params gathered to host and written as a
    single `.npz` (the FULL_STATE_DICT/rank0 analogue) for interchange.
  * `latest_step` + step-numbered directories — actual resume. Health
    evidence snapshots live under a `health/` subdir, which this
    module's root-level scans never see — evidence can neither evict an
    epoch checkpoint from `prune` nor masquerade as the resume point.
"""

from __future__ import annotations

import re
import shutil
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp
from flax import traverse_util

from hyperion_tpu.checkpoint import integrity
from hyperion_tpu.obs import trace as obs_trace
from hyperion_tpu.runtime import dist
from hyperion_tpu.train.state import TrainState
from hyperion_tpu.utils.retry import IO_RETRY, fault_point, retry_call

_STEP_DIR = re.compile(r"^step_(\d+)$")

# The one in-flight async save (ocp.StandardCheckpointer IS an
# AsyncCheckpointer — the old code's `with` block just closed, and
# thereby fenced, it immediately). Holding the state tree until commit
# would pin buffers the train step wants to donate, so the record keeps
# only what the manifest needs: path, step, and the mesh provenance
# captured eagerly at dispatch.
_PENDING: dict | None = None


def wait_pending(tracer=None) -> Path | None:
    """Block until the in-flight async save (if any) commits, then
    write its manifest — the ONLY place a manifest follows an async
    dispatch, which is what makes "manifest present" mean "the bytes
    all landed". Returns the committed path, or None when nothing was
    pending or the commit failed (the dir is left unverified for the
    restore walk-back to arbitrate — exactly like a crash would).

    Emits the `ckpt_commit` half of the async-save span pair;
    `overlap_s` on it is the wall time training ran while the write
    streamed (dispatch return -> commit wait start)."""
    global _PENDING
    if _PENDING is None:
        return None
    pend, _PENDING = _PENDING, None
    tr = tracer or obs_trace.null_tracer()
    ckptr = pend["ckptr"]
    with tr.span("ckpt_commit", step=pend["step"]) as sp:
        sp.set(overlap_s=round(time.perf_counter() - pend["t_dispatch"], 4))
        try:
            ckptr.wait_until_finished()
        except Exception as e:  # noqa: BLE001 — unverified dir, walk on
            sp.set(error=type(e).__name__)
            tr.event("ckpt_commit_failed", step=pend["step"], error=repr(e))
            print(f"[checkpoint] async save at step {pend['step']} failed "
                  f"to commit ({e!r}); {pend['path'].name} stays unverified")
            _close_quiet(ckptr)
            return None
        _close_quiet(ckptr)
        if dist.is_primary():
            integrity.write_manifest(
                pend["path"], step=pend["step"],
                extra={"mesh_shape": pend["mesh_shape"]},
            )
    return pend["path"]


def _close_quiet(ckptr) -> None:
    try:
        ckptr.close()
    except Exception:  # noqa: BLE001 — the save outcome already decided
        pass


def _step_path(root: str | Path, step: int) -> Path:
    return Path(root).absolute() / f"step_{step:08d}"


def _step_dirs(root: Path) -> list[tuple[int, Path]]:
    """(step, path) for every live step dir, ascending, as ABSOLUTE
    paths (orbax rejects relative ones). `step_X.corrupt` quarantine
    dirs and the `health/` evidence subdir don't match."""
    root = Path(root).absolute()
    if not root.is_dir():
        return []
    return sorted(
        (int(m.group(1)), p)
        for p in root.iterdir()
        if (m := _STEP_DIR.match(p.name)) and p.is_dir()
    )


def save(root: str | Path, state: TrainState, force: bool = False,
         wait: bool = True, tracer=None) -> Path:
    """Write a sharded checkpoint at the state's current step, then
    commit it with a manifest (primary process). A dir without a
    manifest is, by definition, a save that never finished — restore's
    walk-back will quarantine it.

    `wait=False` returns after the async dispatch (device arrays
    snapshotted to host — safe even with buffer donation, which is why
    training can keep mutating the state immediately): the disk write
    streams out in the background and the manifest lands at the next
    `wait_pending()` (called here first, so one save is in flight at a
    time, and by every trainer exit path). The default `wait=True`
    keeps the old synchronous contract: dispatch, commit, manifest,
    return."""
    global _PENDING
    wait_pending(tracer=tracer)  # at most one save in flight
    step = int(state.step)
    path = _step_path(root, step)
    attempt = {"n": 0}
    holder: dict = {}
    tr = tracer or obs_trace.null_tracer()

    def _write():
        fault_point("ckpt_save")
        # a retried attempt may land on the partial dir the failed one
        # left behind — force the overwrite there even when the caller
        # didn't ask for one
        f = force or attempt["n"] > 0
        attempt["n"] += 1
        ckptr = ocp.StandardCheckpointer()
        try:
            ckptr.save(path, state, force=f)
            if wait:
                # synchronous contract: commit inside the retry scope,
                # so a transient background-write failure retries the
                # whole save exactly as the old close()-fenced path did
                ckptr.wait_until_finished()
        except BaseException:
            _close_quiet(ckptr)
            raise
        holder["ckptr"] = ckptr

    with tr.span("ckpt_dispatch", step=step) as sp:
        sp.set(wait=wait)
        retry_call(_write, policy=IO_RETRY,
                   on_retry=lambda a, e, d: print(
                       f"[checkpoint] save attempt {a + 1} failed ({e}); "
                       f"retrying in {d:.2f}s"))
    if wait:
        _close_quiet(holder["ckptr"])
        with tr.span("ckpt_commit", step=step) as sp:
            sp.set(overlap_s=0.0)
            if dist.is_primary():
                integrity.write_manifest(path, step=step, state=state)
        return path
    _PENDING = {
        "ckptr": holder["ckptr"],
        "path": path,
        "step": step,
        # provenance captured NOW: holding the state until commit would
        # pin buffers the (donating) train step is about to reuse
        "mesh_shape": integrity.mesh_shape_of(state),
        "t_dispatch": time.perf_counter(),
    }
    return path


def prune(root: str | Path, keep: int = 2) -> None:
    """Delete all but the newest `keep` step directories — an epoch of a
    7B full fine-tune writes tens of GB of params + Adam state, and
    restore only ever reads the newest verified step. Three hygiene
    rules: quarantined `*.corrupt` dirs are never touched (they are
    evidence, and already out of the step namespace); the `health/`
    evidence subdir is invisible here; and the newest VERIFIED dir
    survives even when `keep` would doom it — pruning must never leave
    the tree with only unverifiable checkpoints."""
    root = Path(root)
    dirs = _step_dirs(root)
    if not dirs:
        return
    # shallow verification (manifest + sizes): O(stat) per dir per
    # epoch, not O(checkpoint bytes) — deep hashing belongs to restore
    newest_verified = next(
        (step for step, p in reversed(dirs) if integrity.verify(p, deep=False)[0]),
        None,
    )
    doomed = dirs[:-keep] if keep else dirs
    for step, p in doomed:
        if step == newest_verified:
            continue
        shutil.rmtree(p, ignore_errors=True)


def latest_step(root: str | Path) -> int | None:
    steps = [step for step, _ in _step_dirs(Path(root))]
    return max(steps, default=None)


def _restore_step(path: Path, template: TrainState) -> TrainState:
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        template,
    )

    def _read():
        fault_point("ckpt_restore")
        with ocp.StandardCheckpointer() as ckptr:
            return ckptr.restore(path, target)

    return retry_call(_read, policy=IO_RETRY,
                      on_retry=lambda a, e, d: print(
                          f"[checkpoint] restore attempt {a + 1} failed "
                          f"({e}); retrying in {d:.2f}s"))


def restore(
    root: str | Path, template: TrainState, step: int | None = None,
    tracer=None,
) -> TrainState | None:
    """Restore the newest VERIFIED step directly into the template's
    sharding — each device reads only the shards it owns, so restore
    scales like sharded save did. `template` is a freshly-initialized
    state (the trainer builds one anyway); a checkpoint written on a
    different mesh reshards onto the template's.

    Walk-back: steps are tried newest-first; a dir that fails
    verification (partial save, bit rot, chaos) or errors mid-restore
    is quarantined as `step_X.corrupt` with a reason file and a
    `checkpoint_quarantined` trace event, and the walk continues to the
    prior step. Returns None when nothing restorable remains (fresh
    run). An explicit `step` is verified and restored with no fallback
    — the caller asked for those exact bytes, so failure raises."""
    # an in-flight async save must commit before the walk scans the
    # tree (same-process save->restore sequences would otherwise race
    # the background write)
    wait_pending(tracer=tracer)
    root = Path(root)
    if step is not None:
        path = _step_path(root, step)
        ok, reason = integrity.verify(path)
        if not ok:
            # same legacy allowance as the walk-back below: a committed
            # pre-manifest checkpoint restores; anything else raises
            if not (reason.startswith("missing manifest")
                    and (path / "_CHECKPOINT_METADATA").exists()):
                raise ValueError(
                    f"checkpoint step {step} at {path} failed "
                    f"verification: {reason}")
        return _restore_step(path, template)
    for step, path in reversed(_step_dirs(root)):
        ok, reason = integrity.verify(path)
        # "missing manifest" covers two populations: a partial dir from
        # a crashed save, and every checkpoint written BEFORE manifests
        # existed. Quarantining the latter would silently discard all
        # pre-upgrade progress, so orbax's own commit marker arbitrates:
        # a finalized save has `_CHECKPOINT_METADATA` (written last) —
        # with it, the dir is a committed legacy checkpoint and is
        # adopted (manifest backfilled on successful restore); without
        # it, the save provably never finished. (orbax restore alone
        # cannot arbitrate: it reads damaged dirs without complaint,
        # which is why the manifest layer exists at all.)
        legacy = (reason.startswith("missing manifest")
                  and (path / "_CHECKPOINT_METADATA").exists())
        if ok or legacy:
            try:
                restored = _restore_step(path, template)
            except Exception as e:  # noqa: BLE001 — quarantine + walk on
                reason = (f"{reason}; restore failed: {e!r}" if not ok
                          else f"verified but restore failed: {e!r}")
            else:
                if not ok and dist.is_primary():
                    print(f"[checkpoint] adopted legacy checkpoint "
                          f"{path.name} (no manifest, orbax commit "
                          "marker present); backfilling a manifest")
                    integrity.write_manifest(path, step=step,
                                             state=restored)
                return restored
        elif reason.startswith("missing manifest"):
            reason += " and no orbax commit marker — partial save"
        integrity.quarantine(path, reason, tracer=tracer)
    return None


def export_gathered(path: str | Path, params: Any) -> Path | None:
    """Gather full (unsharded) params to host and write one `.npz` — the
    FULL_STATE_DICT-to-rank-0 analogue (distributed_utils.py:374-386).
    Every process participates in the gather (multi-host shards are not
    locally addressable, so the collective must run everywhere); only the
    primary writes, returning None elsewhere."""

    def to_host(v):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            from jax.experimental import multihost_utils

            v = multihost_utils.process_allgather(v, tiled=True)
        return np.asarray(jax.device_get(v))

    flat = traverse_util.flatten_dict(params, sep="/")
    gathered = {k: to_host(v) for k, v in flat.items()}
    if not dist.is_primary():
        return None
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez(path, **gathered)
    return path


def load_gathered(path: str | Path) -> dict:
    """Read an exported `.npz` back into a nested param dict. The npy
    format has no name for bfloat16 and hands such leaves back as
    2-byte void; `export_gathered` writes no other 2-byte void type, so
    they are viewed as the bfloat16 they were."""
    import ml_dtypes

    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    for k, v in flat.items():
        if v.dtype == np.dtype("V2"):
            flat[k] = v.view(ml_dtypes.bfloat16)
    return traverse_util.unflatten_dict(flat, sep="/")
