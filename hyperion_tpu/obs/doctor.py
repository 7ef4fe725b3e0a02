"""`obs doctor <dir>` — classify a run from its telemetry + heartbeat.

The post-mortem questions a dead capture window always raises — did the
run finish? crash? hang inside backend init? slow down until the stage
timeout killed it? diverge? — are all answerable from artifacts the run
already wrote: the JSONL stream (`obs/trace.py`) and the heartbeat file
(`obs/heartbeat.py`). This module answers them mechanically, so a human
(or a watching script) never re-reads raw logs to learn what a
run's own telemetry already knows.

Verdicts, in evidence order (first match wins):

  diverged  — fatal `health` events (non-finite loss/grads) or a
              health-abort in the stream
  failed    — the run said goodbye while REPORTING failure (a terminal
              event carrying failed=true / an error attr): completed,
              but not healthy
  healthy   — a terminal lifecycle event landed (train_end /
              generate_done / publish); the run said goodbye
  crashed   — no terminal event AND the stream ends mid-write (the
              truncated-tail signature of a killed process) or a span
              recorded an exception
  hung      — no terminal event and the heartbeat (or, absent one, the
              stream itself) went stale: the host loop stopped moving.
              Staleness outranks a stall pattern — a dead process is
              hung however slow its final recorded steps were (the
              stall evidence is appended to the reason)
  stalled   — no terminal event, heartbeat/stream still FRESH, but the
              tail step spans run far slower than the run's own median
              — the loop is alive and degrading (do not kill it; watch)
  running   — no terminal event, heartbeat fresh: leave it alone

Exit codes: 0 healthy/running, 1 failed/crashed/hung/stalled/diverged,
2 unreadable/empty — so shell watchers can branch on `$?`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from hyperion_tpu.obs.heartbeat import heartbeat_age_s, read_heartbeat
from hyperion_tpu.obs.registry import percentile
from hyperion_tpu.obs.tickprof import (
    EXPERT_ROW_COUNTERS,
    FLIGHT_NAME,
    PROMPT_READ_COUNTERS,
    STATE_ROW_COUNTERS,
    WALK_COUNTERS,
    WRITE_COUNTERS,
    flight_final_tick,
    read_flight,
)

_TERMINAL_EVENTS = ("train_end", "generate_done", "publish", "serve_end",
                    "router_end")
_STEP_SPANS = ("train_step", "decode_step", "serve_tick")
_FATAL_KINDS = ("nonfinite_loss", "nonfinite_grad")

# stale thresholds: a heartbeat older than STALE_S with no terminal
# event means the host loop stopped (beats are time-limited to ~15 s by
# Heartbeat.interval_s, so 300 s of silence is ~20 missed beats)
STALE_S = 300.0
STALL_RATIO = 5.0
_STALL_TAIL = 3          # steps averaged for the tail
_STALL_MIN_STEPS = 6     # need a baseline before "slower than usual" means anything
# input-bound threshold: when the step loop spent more than this
# fraction of its last epoch blocked on the input queue (the
# `input_wait_frac` gauge from `observe_input_wait`), the run is
# data-starved — the fix is prefetch depth / faster input, not a
# bigger chip
INPUT_BOUND_FRAC = 0.5
# tail-attribution threshold: a phase owning at least this fraction of
# the p99 cohort's latency (obs/timeline.py) earns a NAMED incident —
# below it, the tail is diffuse and naming one phase would mislead
TAIL_DOMINANT_FRAC = 0.4
# speculative-decoding acceptance floor: below this the k+1-wide verify
# forward is mostly wasted work — the run pays spec overhead for
# roughly sequential progress, so the draft config is a named incident
SPEC_ACCEPT_FLOOR = 0.3
# host-tick-profile threshold (obs/tickprof.py): a NON-device segment
# owning at least this fraction of tick wall earns a named incident —
# the serving loop is then host-bound, and the segment name says where
HOST_SEGMENT_FRAC = 0.4
_HOST_SEGMENT_MIN_TICKS = 8   # below this the window is noise
# host-leak heuristic: peak RSS still climbing at the newest snapshots
# AND up more than this factor over the run — a plateaued process
# (normal warmup growth) fails the "still rising" half
RSS_CLIMB_RATIO = 1.15
_SEGMENT_HINTS = {
    "journal": "slow disk under the request journal (append/fsync)",
    "sink": "slow clients on the transport sinks",
    "queue_pop": "admission-queue contention",
    "admit": "prefill/admission host work",
    "draft": "draft proposal building",
    "bt_upload": "block-table re-uploads — table churning every tick",
    "accept": "token-accept host path",
    "slo": "metrics/SLO evaluation overhead",
    "other": "unattributed host work",
}


def _largest_child(tickprof: dict, seg: str) -> str:
    """` (mostly `admit/blocks`, 57% of it)` for a segment whose
    children (obs/tickprof.py: `parent/child` stretches inside it) the
    profile carries, else ``: the segment says which part of a step is
    slow, the child which call inside it."""
    inside = {k: v for k, v in (tickprof.get("children") or {}).items()
              if k.startswith(seg + "/")}
    whole = ((tickprof.get("segments") or {}).get(seg) or {}).get("s")
    if not inside or not whole:
        return ""
    child = max(inside, key=inside.get)
    return f" (mostly `{child}`, {100 * inside[child] / whole:.0f}% of it)"


def locate(target: str | Path) -> tuple[Path, Path]:
    """(telemetry_path, heartbeat_path) for a run dir or a direct
    telemetry.jsonl path (heartbeat is its sibling)."""
    target = Path(target)
    if target.is_dir():
        return target / "telemetry.jsonl", target / "heartbeat.json"
    return target, target.parent / "heartbeat.json"


def read_stream(path: str | Path) -> tuple[list[dict], int, bool]:
    """(records, n_bad_lines, truncated_tail). Unlike the summarizer's
    reader this keeps the malformed-line evidence: a final line a killed
    process never finished writing is the crash signature."""
    records: list[dict] = []
    bad = 0
    truncated_tail = False
    try:
        lines = Path(path).read_text(encoding="utf-8").splitlines()
    except OSError:
        return [], 0, False
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
            truncated_tail = False
        except json.JSONDecodeError:
            bad += 1
            truncated_tail = i == len(lines) - 1
    return records, bad, truncated_tail


def fleet_evidence(tele_path: Path, events: list[dict],
                   now: float, stale_s: float = STALE_S,
                   ) -> tuple[list[dict], list[str]]:
    """Per-replica evidence for a router run (`hyperion route`): the
    fleet layout puts each replica's artifacts in `replica_<i>/` next
    to the router's stream, so one doctor invocation on the base dir
    can render every replica's state and occupancy — and NAME a dead
    replica instead of letting a silent child hide behind a healthy
    router verdict. Returns (rows, incidents)."""
    base = Path(tele_path).parent
    ejected: dict[str, int] = {}
    readmitted: dict[str, int] = {}
    for e in events:
        rid = e.get("replica")
        if rid is None:
            continue
        if e.get("name") == "replica_ejected":
            ejected[str(rid)] = ejected.get(str(rid), 0) + 1
        elif e.get("name") in ("replica_ready", "replica_readmitted"):
            readmitted[str(rid)] = readmitted.get(str(rid), 0) + 1
    rows: list[dict] = []
    incidents: list[str] = []
    # numeric order: a 10+ replica fleet must not table as 0,1,10,11,2
    for d in sorted(base.glob("replica_*"),
                    key=lambda p: (not p.name.removeprefix(
                        "replica_").isdigit(),
                        int(p.name.removeprefix("replica_"))
                        if p.name.removeprefix("replica_").isdigit()
                        else 0, p.name)):
        if not d.is_dir():
            continue
        idx = d.name.removeprefix("replica_")
        hb = read_heartbeat(d / "heartbeat.json")
        age = heartbeat_age_s(hb, now) if hb else None
        phase = hb.get("phase") if hb else None
        if hb is None:
            state = "no heartbeat"
        elif phase == "done":
            state = "done"
        elif age is not None and age > stale_s:
            state = "dead"
        else:
            state = "beating"
        rows.append({
            "replica": idx, "state": state, "phase": phase,
            "step": hb.get("step") if hb else None,
            "pid": hb.get("pid") if hb else None,
            "attempt": hb.get("attempt") if hb else None,
            "active": hb.get("active") if hb else None,
            "queue": hb.get("queue") if hb else None,
            "age_s": round(age, 1) if age is not None else None,
            "ejections": ejected.get(idx, 0),
        })
        if state == "dead":
            occ = ""
            if hb.get("active") is not None:
                occ = (f" with {hb.get('active')} active + "
                       f"{hb.get('queue')} queued in hand")
            incidents.append(
                f"replica {idx} DEAD — heartbeat stale "
                f"({_age(age)} old, phase {phase!r}{occ}); its journal "
                f"owes replay: check {d.name}/telemetry.jsonl for "
                "journal_replayed on the next start")
        elif state == "no heartbeat":
            incidents.append(
                f"replica {idx} never beat — child failed before its "
                f"first heartbeat; read {d.name}/telemetry.jsonl")
    return rows, incidents


def diagnose(
    target: str | Path,
    *,
    run: str | None = None,
    now: float | None = None,
    stale_s: float = STALE_S,
    stall_ratio: float = STALL_RATIO,
) -> dict:
    """Classify one run (default: the last run in the stream)."""
    tele_path, hb_path = locate(target)
    records, bad_lines, truncated_tail = read_stream(tele_path)
    hb = read_heartbeat(hb_path)
    now = time.time() if now is None else now

    run_ids: dict[str, None] = {}
    for r in records:
        if r.get("run"):
            run_ids.setdefault(r["run"], None)
    if not run_ids:
        return {
            "target": str(target), "run": None, "verdict": "empty",
            "reason": f"no parseable records in {tele_path}",
        }
    run = run or list(run_ids)[-1]
    recs = [r for r in records if r.get("run") == run]
    if not recs:
        return {
            "target": str(target), "run": run, "verdict": "empty",
            "reason": f"run {run!r} not found "
                      f"({len(run_ids)} runs in stream)",
        }
    if hb is not None and hb.get("run") not in (None, run):
        hb = None  # a later run's heartbeat says nothing about this one
    # flight record (obs/tickprof.py): the engine's last spill, living
    # next to the heartbeat — survives any kill the spill preceded
    flight = read_flight(hb_path.parent / FLIGHT_NAME)
    if flight is not None and flight.get("run") not in (None, run):
        flight = None

    events = [r for r in recs if r.get("kind") == "event"]
    spans = [r for r in recs if r.get("kind") == "span"]
    snapshots = [r for r in recs if r.get("kind") == "snapshot"]
    # restart lineage: the supervisor stamps HYPERION_ATTEMPT into each
    # child's train_start event and heartbeat. Lineage spans RUNS (each
    # attempt is its own run id), so it is collected stream-wide.
    attempts = sorted({
        int(r["attempt"]) for r in records
        if r.get("kind") == "event" and r.get("name") == "train_start"
        and isinstance(r.get("attempt"), (int, float))
    })
    attempt = next(
        (int(e["attempt"]) for e in reversed(events)
         if e.get("name") == "train_start"
         and isinstance(e.get("attempt"), (int, float))),
        None,
    )
    if attempt is None and hb is not None \
            and isinstance(hb.get("attempt"), (int, float)):
        attempt = int(hb["attempt"])
    latched = [e for e in events if e.get("name") == "preempt_signal"]
    health = [e for e in events if e.get("name") == "health"]
    fatal = [e for e in health if e.get("anomaly") in _FATAL_KINDS
             or e.get("fatal")]
    terminal = [e for e in events if e.get("name") in _TERMINAL_EVENTS]
    aborted = any(e.get("name") == "health_abort" for e in events) or any(
        str(e.get("preempted")) == "health_abort" for e in terminal
    )
    errored_spans = [s for s in spans if s.get("error")]

    step_spans = [s for s in spans if s.get("name") in _STEP_SPANS
                  and isinstance(s.get("dur_ms"), (int, float))]
    step_ms = [s["dur_ms"] for s in step_spans]
    steps = [s["step"] for s in recs
             if isinstance(s.get("step"), (int, float))]
    last_step = int(max(steps)) if steps else None
    walls = [r["t_wall"] for r in recs
             if isinstance(r.get("t_wall"), (int, float))]
    last_wall = max(walls) if walls else None

    hbm_peak = None
    input_frac = input_wait_s = None
    serve: dict | None = None
    tickprof: dict | None = None
    rss_series: list[float] = []
    for s in snapshots:
        m = s.get("metrics", {})
        g = m.get("gauges", {})
        p = g.get("hbm_peak_mb")
        if p is not None:
            hbm_peak = p if hbm_peak is None else max(hbm_peak, p)
        # host-tick profile rides each serve snapshot as a top-level
        # attr; last snapshot wins ("where is host time going NOW")
        if isinstance(s.get("tickprof"), dict):
            tickprof = s["tickprof"]
        # host RSS as a SERIES across snapshots — the leak warning
        # needs the trend, not the final value
        if isinstance(g.get("host_rss_mb"), (int, float)):
            rss_series.append(float(g["host_rss_mb"]))
        # input-wait evidence: the LAST epoch's snapshot wins (the
        # question is "is it input-bound NOW", not "was it ever")
        if isinstance(g.get("input_wait_frac"), (int, float)):
            input_frac = float(g["input_wait_frac"])
        if isinstance(g.get("input_wait_s"), (int, float)):
            input_wait_s = float(g["input_wait_s"])
        # serving evidence (serve/metrics.py): last snapshot wins here
        # too — occupancy/queue depth answer "what was it doing at the
        # end", counters are cumulative anyway
        c = m.get("counters", {})
        if "serve_ticks" in c or g.get("queue_depth") is not None:
            h = m.get("histograms", {})
            ttft = h.get("ttft_ms") or {}
            serve = {
                "completed": c.get("serve_completed"),
                "rejected": c.get("serve_rejected"),
                "timed_out": c.get("serve_timed_out"),
                "queue_depth": g.get("queue_depth"),
                "slot_occupancy": g.get("slot_occupancy"),
                "tokens_per_s": g.get("tokens_per_s"),
                "ttft_p50_ms": ttft.get("p50"),
                "ttft_p99_ms": ttft.get("p99"),
                # paged-KV-cache pressure (serve/blocks.py)
                "preempted": c.get("serve_preempted"),
                "prefix_lookups": c.get("serve_prefix_lookups"),
                "prefix_hits": c.get("serve_prefix_hits"),
                "prefix_hit_rate": g.get("serve_prefix_hit_rate"),
                "blocks_in_use": g.get("serve_blocks_in_use"),
                "hbm_per_req_mb": g.get("serve_hbm_per_req_mb"),
                # tiered KV cache (PR 20, serve/hostcache.py): where
                # prefix lookups landed and what the host tier moved
                "blocks_evicted": c.get("serve_blocks_evicted"),
                "tier_hits_device": c.get("serve_tier_hits_device"),
                "tier_hits_host": c.get("serve_tier_hits_host"),
                "tier_miss": c.get("serve_tier_miss"),
                "tier_hit_rate_host": g.get("serve_tier_hit_rate_host"),
                "host_spilled_blocks": c.get("serve_host_spilled_blocks"),
                "host_restored_blocks":
                    c.get("serve_host_restored_blocks"),
                "host_cache_mb": g.get("serve_host_cache_mb"),
                # crash safety + overload (serve/journal.py, brownout)
                "shed": c.get("serve_shed"),
                "brownout_clamped": c.get("serve_brownout_clamped"),
                "brownout_active": g.get("serve_brownout_active"),
                "replayed": c.get("serve_replayed"),
                "poisoned": c.get("serve_poisoned"),
                "journal_errors": c.get("serve_journal_errors"),
                "dropped_sinks": c.get("serve_dropped_sinks"),
                # SLO burn-rate alerting (obs/slo.py)
                "alerts_raised": c.get("serve_alerts_raised"),
                "alerts_active": g.get("serve_alerts_active"),
                # speculative decoding (serve/draft.py + engine spec tick)
                "spec_drafted": c.get("serve_spec_drafted"),
                "spec_accepted": c.get("serve_spec_accepted"),
                "spec_rejected": c.get("serve_spec_rejected"),
                "accept_rate": g.get("serve_spec_accept_rate"),
                "tokens_per_tick": g.get("serve_tokens_per_tick"),
                # compile ledger (obs/ledger.py)
                "recompiles": c.get("serve_recompiles"),
            }
    if tickprof is None and flight is not None \
            and isinstance(flight.get("tickprof"), dict):
        # a killed process may never have snapshotted: the flight
        # record's windowed breakdown is the fallback evidence
        tickprof = flight["tickprof"]

    # ---- stall signal: tail steps vs the run's own earlier median ----
    stall = None
    if len(step_ms) >= _STALL_MIN_STEPS:
        tail = step_ms[-_STALL_TAIL:]
        base = step_ms[:-_STALL_TAIL]
        base_med = percentile(base, 50)
        tail_mean = sum(tail) / len(tail)
        if base_med > 0 and tail_mean >= stall_ratio * base_med:
            stall = {"tail_mean_ms": round(tail_mean, 3),
                     "baseline_p50_ms": round(base_med, 3),
                     "ratio": round(tail_mean / base_med, 1)}

    hb_age = heartbeat_age_s(hb, now) if hb else None
    stream_age = (now - last_wall) if last_wall is not None else None
    stale = (
        hb_age > stale_s if hb_age is not None
        else stream_age is not None and stream_age > stale_s
    )

    # ------------------------------------------------------- verdict
    if fatal or aborted:
        verdict = "diverged"
        reason = (
            f"{len(fatal)} fatal health event(s) "
            f"({', '.join(sorted({e.get('anomaly', '?') for e in fatal}))})"
            + ("; run aborted by health policy" if aborted else "")
        )
    elif any(e.get("failed") or e.get("error") for e in terminal):
        # the run completed its lifecycle but REPORTED failure — a
        # reported failure must not read as healthy
        bad = [e for e in terminal if e.get("failed") or e.get("error")][-1]
        verdict = "failed"
        reason = (f"terminal event {bad.get('name')!r} reported failure"
                  + (f": {bad.get('error')}" if bad.get("error") else ""))
    elif terminal:
        verdict = "healthy"
        reason = f"terminal event {terminal[-1].get('name')!r} recorded"
    elif truncated_tail or errored_spans:
        verdict = "crashed"
        reason = (
            "stream ends mid-write (process killed during a record)"
            if truncated_tail else
            f"span {errored_spans[-1].get('name')!r} recorded "
            f"{errored_spans[-1].get('error')!r}"
        )
        if latched:
            # the guard latched a signal before death: this is a
            # preemption whose grace window ran out mid-shutdown, not
            # an unprovoked crash — a supervisor should just resume
            reason += (f"; preemption signal had latched at step "
                       f"{latched[-1].get('step')} — died during "
                       "shutdown, not unprovoked")
    elif stale:
        # Staleness outranks the stall signal: "stalled" means the loop
        # is alive-and-degrading (watch it, don't kill it) — a process
        # that stopped beating long ago is dead however slow its final
        # recorded steps were.
        verdict = "hung"
        if hb_age is not None:
            reason = (f"heartbeat stale: last beat {_age(hb_age)} ago "
                      f"(phase {hb.get('phase')!r}, step {hb.get('step')}), "
                      "no terminal event")
        else:
            reason = (f"no heartbeat file; stream silent for "
                      f"{_age(stream_age)} with no terminal event")
        if stall:
            reason += (f"; tail steps had degraded {stall['ratio']}x "
                       "before the loop stopped")
        if latched:
            reason += (f"; preemption signal had latched at step "
                       f"{latched[-1].get('step')} — died during "
                       "shutdown, not unprovoked")
    elif stall:
        verdict = "stalled"
        reason = (
            f"tail steps {stall['ratio']}x slower than the run's own "
            f"p50 ({stall['tail_mean_ms']} vs {stall['baseline_p50_ms']} ms)"
        )
    elif hb_age is not None:
        verdict = "running"
        reason = (f"heartbeat fresh ({_age(hb_age)} ago, "
                  f"phase {hb.get('phase')!r}, step {hb.get('step')})")
    else:
        verdict = "running"
        reason = "stream active, no terminal event yet"

    # Flight-record citation (obs/tickprof.py): for a dead process the
    # record's final ticks are the best evidence of what the loop was
    # doing when it stopped — cite them in the verdict itself.
    flight_summary = None
    if flight is not None:
        ftick = flight_final_tick(flight)
        ftp = flight.get("tickprof") or {}
        flight_summary = {
            "final_tick": ftick,
            "reason": flight.get("reason"),
            "spills": flight.get("spills"),
            "active": flight.get("active"),
            "queue": flight.get("queue"),
            "dominant": ftp.get("dominant"),
            "dominant_frac": ftp.get("dominant_frac"),
        }
        if verdict in ("crashed", "hung"):
            seg_txt = ""
            if ftp.get("dominant"):
                seg_txt = (f", dominant segment {ftp['dominant']} "
                           f"{100 * (ftp.get('dominant_frac') or 0):.0f}%")
            reason += (
                f"; flight record: last spill at tick {_fmt(ftick)} "
                f"(reason={flight.get('reason')!r}, "
                f"{_fmt(flight.get('active'))} active + "
                f"{_fmt(flight.get('queue'))} queued{seg_txt})")

    # Orthogonal to liveness: a run can be perfectly healthy AND
    # input-bound — compute idling while the host assembles batches.
    # Appended to the reason, not a verdict of its own: the verdicts
    # answer "is it alive", this answers "is it fed".
    input_bound = input_frac is not None and input_frac >= INPUT_BOUND_FRAC
    if input_bound and verdict in ("healthy", "running", "stalled"):
        reason += (
            f"; input-bound: {100 * input_frac:.0f}% of the last epoch "
            "was spent blocked on the input pipeline "
            f"({input_wait_s:.2f}s waiting)" if input_wait_s is not None
            else f"; input-bound: input_wait_frac={input_frac:.2f}"
        )

    # Cache-pressure incidents (paged serve KV cache) — also orthogonal
    # to liveness: a run that preempted its way through an undersized
    # pool "completes", just slowly, and a shared-prefix workload that
    # never hit the prefix cache silently re-prefilled every prompt.
    # Both are sizing/config bugs worth naming, not just slow numbers.
    cache_pressure: list[str] = []
    if serve and serve.get("preempted"):
        cache_pressure.append(
            f"{int(serve['preempted'])} pool-exhaustion preemption(s) — "
            "--num-blocks likely undersized for this load")
    shared_wl = next(
        (e for e in events if e.get("name") == "serve_workload"
         and e.get("shared_prefix_tokens")), None)
    if shared_wl is not None and serve and serve.get("prefix_lookups") \
            and not serve.get("prefix_hits"):
        cache_pressure.append(
            f"shared-prefix workload ({shared_wl['shared_prefix_tokens']} "
            "common tokens) saw ZERO prefix hits — prefix cache disabled "
            "or --block-size larger than the shared prefix")
    if cache_pressure and verdict in ("healthy", "running", "stalled",
                                      "failed"):
        reason += "; cache pressure: " + "; ".join(cache_pressure)

    # Cache-TIER incidents (PR 20, serve/hostcache.py): device
    # evictions are survivable exactly when the host spill tier
    # catches them. The serve_start header says whether the tier was
    # ON (--host-cache-mb), `host_restore` events say it actually fed
    # re-hits, and `hostcache_saved` / `hostcache_loaded` events prove
    # the store survived a drain/restart cycle — so "disabled" and
    # "undersized" are DIFFERENT named incidents with different knobs.
    tier_incidents: list[str] = []
    start_ev = next((e for e in reversed(events)
                     if e.get("name") == "serve_start"), None)
    tier_mb = (start_ev or {}).get("host_cache_mb")
    restore_events = sum(1 for e in events
                         if e.get("name") == "host_restore")
    saved_ev = next((e for e in reversed(events)
                     if e.get("name") == "hostcache_saved"), None)
    loaded_ev = next((e for e in reversed(events)
                      if e.get("name") == "hostcache_loaded"), None)
    evicted = int((serve or {}).get("blocks_evicted") or 0)
    spilled = int((serve or {}).get("host_spilled_blocks") or 0)
    host_hits = int((serve or {}).get("tier_hits_host") or 0)
    if evicted and tier_mb is not None and not tier_mb:
        tier_incidents.append(
            f"{evicted} KV block(s) evicted with the host tier "
            "DISABLED — evicted prefixes re-prefill from scratch on "
            "re-hit; set --host-cache-mb to spill them to host RAM")
    elif tier_mb and spilled and not host_hits \
            and int((serve or {}).get("tier_miss") or 0):
        tier_incidents.append(
            f"host tier spilled {spilled} block(s) but fed ZERO "
            "re-hits while prefix lookups still missed — "
            "--host-cache-mb likely undersized (spilled chains "
            "LRU-evicted before the workload came back for them)")
    host_tier = None
    if tier_mb or spilled or restore_events or saved_ev or loaded_ev:
        host_tier = {
            "budget_mb": tier_mb,
            "restore_events": restore_events,
            "saved": ({"chains": saved_ev.get("chains"),
                       "mb": saved_ev.get("mb")} if saved_ev else None),
            "loaded": ({"chains": loaded_ev.get("chains"),
                        "mb": loaded_ev.get("mb")} if loaded_ev
                       else None),
        }
    if tier_incidents and verdict in ("healthy", "running", "stalled",
                                      "failed"):
        reason += "; cache tier: " + "; ".join(tier_incidents)

    # Low-acceptance speculation incident (spec-enabled runs only): when
    # drafts mostly miss, every decode tick still pays the k+1-wide
    # verify forward but advances roughly one token — worse than plain
    # sequential decode. That is a draft-config bug worth naming with
    # the exact knobs to turn, not a number to eyeball in a gauge dump.
    spec_issues: list[str] = []
    if serve and serve.get("spec_drafted"):
        rate = serve.get("accept_rate")
        if rate is not None and rate < SPEC_ACCEPT_FLOOR:
            spec_issues.append(
                f"draft acceptance {rate:.2f} < {SPEC_ACCEPT_FLOOR}: "
                "draft mispredicting — lower --spec-k or disable --draft")
    if spec_issues and verdict in ("healthy", "running", "stalled",
                                   "failed"):
        reason += "; speculation: " + "; ".join(spec_issues)

    # Overload + crash-safety incidents (PR 8): shed/clamped requests
    # mean the brownout governor fired — the server DEGRADED instead of
    # collapsing, which is working as designed but is still a capacity
    # fact the operator must hear by name; poisoned requests and
    # journal IO errors are robustness events that must never hide
    # inside aggregate counters.
    overload: list[str] = []
    if serve and serve.get("shed"):
        overload.append(
            f"overload brownout shed {int(serve['shed'])} "
            "deadline-doomed request(s) — offered load exceeded "
            "capacity; raise --slots, add replicas, or loosen deadlines")
    if serve and serve.get("brownout_clamped"):
        overload.append(
            f"brownout clamped max_new_tokens on "
            f"{int(serve['brownout_clamped'])} admission(s)")
    if serve and serve.get("brownout_active"):
        overload.append("brownout still ACTIVE at the last snapshot — "
                        "the run ended under overload")
    poisoned_ids = [str(e.get("request")) for e in events
                    if e.get("name") == "request_poisoned"]
    if poisoned_ids:
        overload.append(
            f"poison pill: request(s) {', '.join(sorted(poisoned_ids))} "
            "quarantined after repeated crash-replays — inspect the "
            "journal before re-submitting them")
    if serve and serve.get("journal_errors"):
        overload.append(
            "request journal hit an IO error and was DISABLED — the "
            "run served on without crash recovery")
    if overload and verdict in ("healthy", "running", "stalled",
                                "failed", "crashed", "hung"):
        reason += "; serving robustness: " + "; ".join(overload)

    # SLO burn-rate alerts (obs/slo.py): the engine/router loops emit
    # alert_raised/alert_cleared transitions; the doctor tallies them
    # per alert name so a firing alert is a NAMED incident — with the
    # metric, threshold, and the burn that tripped it — and a raised-
    # then-cleared alert reads as a resolved incident, not noise.
    slo_incidents: list[str] = []
    by_alert: dict[str, dict] = {}
    for e in events:
        if e.get("name") not in ("alert_raised", "alert_cleared"):
            continue
        name = str(e.get("alert"))
        row = by_alert.setdefault(name, {
            "alert": name, "metric": e.get("metric"),
            "threshold": e.get("threshold"),
            "raised": 0, "cleared": 0, "active": False,
            "last_value": None, "active_s": None,
        })
        if e.get("name") == "alert_raised":
            row["raised"] += 1
            row["active"] = True
            row["last_value"] = e.get("fast")
        else:
            row["cleared"] += 1
            row["active"] = False
            row["active_s"] = e.get("active_s")
    slo_alerts = list(by_alert.values())
    for row in slo_alerts:
        if row["active"]:
            tail = ("never cleared" if not row["cleared"]
                    else f"cleared {row['cleared']}x, re-raised")
            slo_incidents.append(
                f"SLO alert '{row['alert']}' FIRING "
                f"({row['metric']} {_fmt(row['last_value'])} vs target "
                f"{_fmt(row['threshold'])}; raised {row['raised']}x, "
                f"{tail})")
        else:
            slo_incidents.append(
                f"SLO alert '{row['alert']}' raised {row['raised']}x "
                f"and cleared (last burn lasted "
                f"{_fmt(row['active_s'])}s)")
    if slo_incidents and verdict in ("healthy", "running", "stalled",
                                     "failed", "crashed", "hung"):
        reason += "; slo: " + "; ".join(slo_incidents)

    # Replica-fleet evidence (serve/router.py layout): a router run's
    # own stream can be perfectly healthy while one of its children is
    # dead — the fleet table makes each replica's state/occupancy a
    # first-class evidence row, and a dead replica is a NAMED incident,
    # not a throughput mystery.
    fleet_rows, fleet_incidents = fleet_evidence(
        tele_path, events, now, stale_s=stale_s)
    if fleet_incidents:
        reason += "; fleet: " + "; ".join(fleet_incidents)

    # Cross-process tail attribution (the hop-context join): with
    # replica dirs on disk and completed relays on this stream, the
    # fleet assembler decomposes CLIENT-observed tails into router /
    # wire / replica / failover components — the dominant one names an
    # incident no single process's own attribution can see ("p99 e2e
    # dominated by failover_gap — replica restarts too slow").
    fleet_trace_rows: list[dict] = []
    fleet_trace_incidents: list[str] = []
    if fleet_rows and any(e.get("name") == "route_complete"
                          for e in events):
        try:
            from hyperion_tpu.obs import fleet_trace as fleet_mod

            asm = fleet_mod.assemble(Path(tele_path).parent)
            if asm is not None:
                att = fleet_mod.attribution(asm)
                fleet_trace_rows = att["rows"]
                fleet_trace_incidents = fleet_mod.tail_incidents(
                    att["rows"])
        except Exception:  # noqa: BLE001 — partial fleet evidence must
            pass           # degrade the join, never the diagnosis
    if fleet_trace_incidents and verdict in (
            "healthy", "running", "stalled", "failed", "crashed",
            "hung"):
        reason += "; fleet trace: " + "; ".join(fleet_trace_incidents)

    # Router WAL post-mortem (PR 15): a dead router LIFE leaves its
    # dispatch WAL next to the stream — pending (dispatched, never
    # terminal) entries are the streams it still owes clients, and the
    # WAL tail is the crash's own evidence. Read-only: the next router
    # life, not the doctor, performs the recovery.
    router_wal: dict | None = None
    wal_path = Path(tele_path).parent / "router_journal.jsonl"
    if wal_path.exists():
        try:
            from hyperion_tpu.serve.router_journal import RouterJournal

            wal = RouterJournal(wal_path)
            router_wal = {"path": str(wal_path),
                          "pending": wal.pending_count(),
                          "tail": wal.tail(5)}
        except Exception:  # noqa: BLE001 — a torn WAL must not kill
            router_wal = None   # the diagnosis reading it
    if router_wal and router_wal["pending"] > 0 \
            and not any(e.get("name") == "router_end" for e in events):
        tail_s = "; ".join(
            f"{r.get('k')}"
            + (f" {r.get('id')}" if r.get("id") else "")
            + (f" i={r.get('i')}" if r.get("k") == "hwm" else "")
            + (f" replica={r.get('replica')}"
               if r.get("k") == "dispatch" else "")
            for r in router_wal["tail"])
        incident = (
            f"router died owing {router_wal['pending']} in-flight "
            f"stream(s) — the dispatch WAL ({wal_path.name}) holds "
            f"their placements and high-water marks (tail: {tail_s}); "
            "a supervised restart re-adopts live replicas and resumes "
            "them exactly-once")
        router_wal["incident"] = incident
        if verdict in ("healthy", "running", "stalled", "failed",
                       "crashed", "hung"):
            reason += "; router WAL: " + incident

    # Hostile-tenant attribution (PR 14): adversarial workload profiles
    # tag their requests with a tenant label, and the engine's
    # admit/shed events carry it through — so when a run degraded, the
    # doctor can NAME the workload that drove it instead of describing
    # anonymous pressure. Ranked by damage (sheds+rejects, then
    # volume): the top row is the offender.
    tenant_rows: dict[str, dict] = {}
    for e in events:
        t = e.get("tenant")
        if not t:
            continue
        row = tenant_rows.setdefault(str(t), {
            "tenant": str(t), "admitted": 0, "shed": 0, "rejected": 0,
            "classes": set()})
        if e.get("sla_class"):
            row["classes"].add(str(e["sla_class"]))
        if e.get("name") == "request_admitted":
            row["admitted"] += 1
        elif e.get("name") == "request_rejected":
            row["shed" if e.get("shed") else "rejected"] += 1
    tenants = sorted(tenant_rows.values(),
                     key=lambda r: (-(r["shed"] + r["rejected"]),
                                    -r["admitted"], r["tenant"]))
    for r in tenants:
        r["classes"] = sorted(r["classes"])
    tenant_incidents: list[str] = []
    if tenants:
        top = tenants[0]
        desc = f"{top['admitted']} admitted"
        if top["shed"]:
            desc += f", {top['shed']} shed"
        if top["rejected"]:
            desc += f", {top['rejected']} rejected"
        hostile = bool(overload) or top["shed"] or top["rejected"]
        tenant_incidents.append(
            (f"tenant '{top['tenant']}' drove the pressure ({desc})"
             if hostile else
             f"tenant '{top['tenant']}' tagged traffic ({desc})"))
        for r in tenants[1:]:
            tenant_incidents.append(
                f"tenant '{r['tenant']}': {r['admitted']} admitted, "
                f"{r['shed']} shed, {r['rejected']} rejected")
    if tenant_incidents and verdict in ("healthy", "running", "stalled",
                                        "failed", "crashed", "hung"):
        reason += "; tenants: " + "; ".join(tenant_incidents)

    # Router-action narration (PR 14): the acting router leaves a
    # telemetry trail (router_steer / router_scale / class_brownout) —
    # the doctor rolls it into prose so "what did the fleet DO about
    # the burn" is one read, not an event grep.
    router_actions: list[str] = []
    steers = [e for e in events if e.get("name") == "router_steer"]
    if steers:
        on = [e for e in steers if e.get("on")]
        off = [e for e in steers if not e.get("on")]
        reps = sorted({e.get("replica") for e in on})
        router_actions.append(
            f"steered interactive traffic off replica(s) "
            f"{', '.join(str(i) for i in reps)} ({len(on)} steer(s), "
            f"{len(off)} unsteer(s)"
            + (" — still steered at the end" if len(on) > len(off)
               else ", all reversed") + ")")
    cbr = [e for e in events if e.get("name") == "class_brownout"]
    if cbr:
        ordered = sum(1 for e in cbr if e.get("active"))
        router_actions.append(
            f"batch-class brownout ordered {ordered}x, lifted "
            f"{len(cbr) - ordered}x")
    scales = [e for e in events if e.get("name") == "router_scale"]
    if scales:
        ups = sum(1 for e in scales if e.get("direction") == "up")
        router_actions.append(
            f"alert-driven scaling: {ups} standby spawn(s), "
            f"{len(scales) - ups} retire(s)")
    if router_actions and verdict in ("healthy", "running", "stalled",
                                      "failed", "crashed", "hung"):
        reason += "; router actions: " + "; ".join(router_actions)

    # Flight-simulator runs (serve/simulate.py): the discrete-event
    # harness stamps its scenario header and assertion verdict into the
    # same stream, so a sim run diagnoses like a live one — plus one
    # extra row saying whether the scenario's obs-plane assertions
    # held. A failed sim check is a POLICY regression, not an outage.
    sim: dict | None = None
    sim_hdr = next((e for e in reversed(events)
                    if e.get("name") == "sim_scenario"), None)
    sim_rep = next((e for e in reversed(events)
                    if e.get("name") == "sim_report"), None)
    if sim_hdr or sim_rep:
        sim = {
            "scenario": (sim_hdr or sim_rep).get("scenario"),
            "replicas": (sim_hdr or {}).get("replicas"),
            "requests": (sim_hdr or {}).get("requests"),
            "duration_s": (sim_hdr or {}).get("duration_s"),
            "seed": (sim_hdr or {}).get("seed"),
            "ok": (sim_rep or {}).get("ok"),
            "checks": (sim_rep or {}).get("checks"),
            "failed": (sim_rep or {}).get("failed"),
            "failed_checks": (sim_rep or {}).get("failed_checks") or [],
            "report": (sim_rep or {}).get("report"),
        }
        if sim_rep is None:
            sim["incident"] = (
                f"simulation '{sim['scenario']}' emitted no verdict — "
                "the harness died mid-scenario")
        elif not sim["ok"]:
            sim["incident"] = (
                f"simulation '{sim['scenario']}' failed "
                f"{sim['failed']}/{sim['checks']} assertion(s): "
                + "; ".join(sim["failed_checks"]))
        else:
            sim["incident"] = None
        if sim["incident"] and verdict in (
                "healthy", "running", "stalled", "failed", "crashed",
                "hung"):
            reason += "; sim: " + sim["incident"]

    # Tail-attribution incidents (obs/timeline.py): the request-scoped
    # trace says WHERE the p99 went, so the doctor can name the FIX —
    # "raise --slots" and "raise --num-blocks" are different knobs a
    # bare p99 number cannot choose between.
    tail_rows: list[dict] = []
    tail_incidents: list[str] = []
    tail_incident_metrics: list[str] = []
    if any(e.get("name") == "request_finished" for e in events):
        from hyperion_tpu.obs import timeline

        att = timeline.attribution(timeline.requests_from_records(
            recs, run=run))
        tail_rows = att["rows"]
        for row in tail_rows:
            if row["q"] != 99 or not row.get("dominant"):
                continue
            if (row.get("dominant_frac") or 0.0) < TAIL_DOMINANT_FRAC:
                continue
            dom = row["dominant"]
            where = (f"{row['components_ms'].get(dom, row['other_ms'])}"
                     f" of {row['value_ms']} ms")
            msg = None
            if row["metric"] == "ttft" and dom == "queue_wait":
                msg = (f"p99 TTFT dominated by queue wait ({where}) — "
                       "raise --slots or tighten admission")
            elif dom == "gate_wait":
                msg = (f"p99 {row['metric']} dominated by block-gate "
                       f"wait ({where}) — raise --num-blocks")
            elif row["metric"] == "e2e" and dom == "preempt_replay":
                if serve and serve.get("replayed") \
                        and not serve.get("preempted"):
                    # same attribution bucket, different culprit: these
                    # replays were crash recoveries (journal), not
                    # pool-exhaustion preemptions — resizing the pool
                    # would fix nothing
                    msg = (f"p99 e2e dominated by replay ({where}) — "
                           "crash-recovery replays (restart cost), not "
                           "pool pressure")
                else:
                    msg = (f"p99 e2e dominated by preempt replay "
                           f"({where}) — --num-blocks undersized for "
                           "this load")
            elif row["metric"] == "e2e" and dom == "client_write":
                msg = (f"p99 e2e dominated by client writes ({where}) "
                       "— slow consumer, not a slow engine")
            if msg is not None:
                tail_incidents.append(msg)
                # the metric rides structurally next to the message so
                # the renderer can flag the RIGHT attribution row
                # without parsing incident prose
                tail_incident_metrics.append(row["metric"])
        tail_incidents = list(dict.fromkeys(tail_incidents))
        tail_incident_metrics = list(dict.fromkeys(tail_incident_metrics))
    if tail_incidents and verdict in ("healthy", "running", "stalled",
                                      "failed"):
        reason += "; tail attribution: " + "; ".join(tail_incidents)

    # Recompile incident (obs/ledger.py): post-warmup jit-cache growth
    # is a broken invariant — name the executable and the churn context
    # ONCE however many times it fired, so the incident reads as one
    # diagnosis, not a stutter.
    recompile_events = [e for e in events
                        if e.get("name") == "recompile_after_warmup"]
    recompile_incidents: list[str] = []
    if recompile_events:
        execs = sorted({str(e.get("executable"))
                        for e in recompile_events})
        total = (int(serve["recompiles"])
                 if serve and isinstance(serve.get("recompiles"),
                                         (int, float))
                 and serve["recompiles"]
                 else len(recompile_events))
        last = recompile_events[-1]
        ctx = ""
        if last.get("last_prefill_bucket") is not None:
            ctx = (f"; last prefill bucket "
                   f"{last['last_prefill_bucket']}, "
                   f"tick {_fmt(last.get('tick'))}")
        recompile_incidents.append(
            f"recompile after warmup: {total} new executable(s) in "
            f"{', '.join(execs)}{ctx} — a shape escaped the warmup "
            "ladder; extend warmup prompt_lens or check the bucket "
            "config")
    if recompile_incidents and verdict in ("healthy", "running",
                                           "stalled", "failed",
                                           "crashed", "hung"):
        reason += "; compile: " + "; ".join(recompile_incidents)

    # Dominant-host-segment incident (obs/tickprof.py): when a NON-
    # device segment owns the tick wall, tokens/s is host-bound and the
    # segment name says exactly where ("journal owns 61% — slow disk").
    host_segment_incidents: list[str] = []
    if tickprof and (tickprof.get("ticks") or 0) >= _HOST_SEGMENT_MIN_TICKS:
        dom = tickprof.get("dominant")
        frac = tickprof.get("dominant_frac") or 0.0
        if dom and dom != "device" and frac >= HOST_SEGMENT_FRAC:
            host_segment_incidents.append(
                f"host segment '{dom}'{_largest_child(tickprof, dom)} "
                f"owns {100 * frac:.0f}% of tick "
                f"time over the last {tickprof.get('ticks')} tick(s) — "
                f"{_SEGMENT_HINTS.get(dom, 'host-side work')}")
    if host_segment_incidents and verdict in ("healthy", "running",
                                              "stalled", "failed",
                                              "crashed", "hung"):
        reason += "; host profile: " + "; ".join(host_segment_incidents)

    # Host RSS trend (heartbeat/engine rss_mb): ru_maxrss is a peak, so
    # it never falls — the leak signal is a peak STILL RISING at the
    # newest snapshots after a material climb, which steady-state
    # serving (plateaued after warmup) stops doing.
    rss_trend = None
    rss_warning = None
    if rss_series:
        rss_trend = {"first_mb": round(rss_series[0], 1),
                     "last_mb": round(rss_series[-1], 1),
                     "samples": len(rss_series)}
        if len(rss_series) >= 4 and rss_series[0] > 0:
            climb = rss_series[-1] / rss_series[0]
            t3 = rss_series[-3:]
            if climb > RSS_CLIMB_RATIO and t3[0] < t3[1] < t3[2]:
                rss_warning = (
                    f"host RSS climbing monotonically "
                    f"({rss_series[0]:.0f} -> {rss_series[-1]:.0f} MB, "
                    f"x{climb:.2f}, still rising at the last 3 "
                    "snapshots) — possible host-side leak")
    if rss_warning and verdict in ("healthy", "running", "stalled",
                                   "failed"):
        reason += "; memory: " + rss_warning

    last_span = spans[-1] if spans else None
    return {
        "target": str(target),
        "telemetry": str(tele_path),
        "run": run,
        "runs_in_file": len(run_ids),
        "verdict": verdict,
        "reason": reason,
        "records": len(recs),
        "bad_lines": bad_lines,
        "truncated_tail": truncated_tail,
        "last_step": last_step,
        "attempt": attempt,
        "attempts": attempts,
        "steps": len(step_ms),
        "step_time_ms": {
            "p50": percentile(step_ms, 50),
            "p99": percentile(step_ms, 99),
        } if step_ms else None,
        "stall": stall,
        "input_bound": input_bound,
        "input_wait_frac": input_frac,
        "input_wait_s": input_wait_s,
        "last_span": {
            "name": last_span.get("name"), "step": last_span.get("step"),
            "dur_ms": last_span.get("dur_ms"),
        } if last_span else None,
        "events": _counts(events),
        "health_events": [
            {"anomaly": e.get("anomaly"), "step": e.get("step"),
             "value": e.get("value"), "action": e.get("action")}
            for e in health
        ],
        "hbm_peak_mb": hbm_peak,
        "serve": serve,
        "slo_alerts": slo_alerts,
        "slo_incidents": slo_incidents,
        "fleet": fleet_rows,
        "fleet_incidents": fleet_incidents,
        # cross-process trace join (PR 16): client-observed tails
        # decomposed across router, wire, replicas, and failover
        "fleet_trace": fleet_trace_rows,
        "fleet_trace_incidents": fleet_trace_incidents,
        # router crash safety (PR 15): the dispatch WAL's post-mortem
        "router_wal": router_wal,
        # workload-isolation plane (PR 14): who drove the pressure and
        # what the acting router did about it
        "tenants": tenants,
        "tenant_incidents": tenant_incidents,
        "router_actions": router_actions,
        # flight simulator (serve/simulate.py): scenario header and
        # assertion verdict from a discrete-event fleet run
        "sim": sim,
        "cache_pressure": cache_pressure,
        # tiered KV cache (serve/hostcache.py): spill-tier evidence
        # and the disabled-vs-undersized incident split
        "tier_incidents": tier_incidents,
        "host_tier": host_tier,
        "spec_incidents": spec_issues,
        "overload": overload,
        "poisoned_requests": poisoned_ids,
        "tail_attribution": tail_rows,
        "tail_incidents": tail_incidents,
        "tail_incident_metrics": tail_incident_metrics,
        # introspection plane (obs/ledger.py, obs/tickprof.py)
        "tickprof": tickprof,
        "recompile_incidents": recompile_incidents,
        "host_segment_incidents": host_segment_incidents,
        "rss_trend": rss_trend,
        "rss_warning": rss_warning,
        "flight": flight_summary,
        "heartbeat": {
            "phase": hb.get("phase"), "step": hb.get("step"),
            "pid": hb.get("pid"), "beats": hb.get("beats"),
            "age_s": round(hb_age, 1) if hb_age is not None else None,
            # serve-loop payload (engine beats): occupancy at the last
            # beat — the hung-vs-slow call needs to know whether the
            # loop froze with work in hand
            "active": hb.get("active"), "queue": hb.get("queue"),
            # live-plane payload: the alerts list the serving loop
            # stamps on its beats (obs/slo.py)
            "alerts": hb.get("alerts"),
        } if hb else None,
    }


def _counts(events: list[dict]) -> dict[str, int]:
    out: dict[str, int] = {}
    for e in events:
        out[e.get("name", "?")] = out.get(e.get("name", "?"), 0) + 1
    return out


def _age(s: float) -> str:
    if s < 120:
        return f"{s:.0f}s"
    if s < 7200:
        return f"{s / 60:.0f}m"
    if s < 48 * 3600:
        return f"{s / 3600:.1f}h"
    return f"{s / 86400:.1f}d"


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def render_markdown(d: dict) -> str:
    if d["verdict"] == "empty":
        return (f"## Run doctor — `{d['target']}`\n\n"
                f"**verdict: empty** — {d['reason']}\n")
    lines = [
        f"## Run doctor — run `{d['run']}`",
        "",
        f"**verdict: {d['verdict']}** — {d['reason']}",
        "",
        f"`{d['telemetry']}` · {d['records']} records"
        + (f" · {d['runs_in_file']} runs in file"
           if d["runs_in_file"] > 1 else "")
        + (f" · {d['bad_lines']} unparseable line(s)"
           if d["bad_lines"] else ""),
        "",
        "| evidence | value |",
        "|---|---|",
        f"| last step | {_fmt(d['last_step'])} |",
        f"| step spans | {d['steps']} |",
    ]
    if d.get("attempts") and (len(d["attempts"]) > 1 or max(d["attempts"])):
        lineage = "→".join(str(a) for a in d["attempts"])
        lines.append(
            f"| restart lineage | attempts {lineage} "
            f"({len(d['attempts'])} launch(es); this run is attempt "
            f"{_fmt(d.get('attempt'))}) |")
    st = d.get("step_time_ms")
    if st:
        lines.append(f"| step time p50 / p99 | {_fmt(st['p50'])} / "
                     f"{_fmt(st['p99'])} ms |")
    if d.get("stall"):
        s = d["stall"]
        lines.append(f"| stall | tail {s['tail_mean_ms']} ms vs p50 "
                     f"{s['baseline_p50_ms']} ms ({s['ratio']}x) |")
    if d.get("input_wait_frac") is not None:
        flag = " — **input-bound**" if d.get("input_bound") else ""
        lines.append(
            f"| input wait | {100 * d['input_wait_frac']:.0f}% of the "
            f"last epoch{flag} |")
    ls = d.get("last_span")
    if ls:
        where = f" (step {ls['step']})" if ls.get("step") is not None else ""
        lines.append(f"| last span | `{ls['name']}`{where}: "
                     f"{_fmt(ls['dur_ms'])} ms |")
    if d.get("hbm_peak_mb") is not None:
        lines.append(f"| peak HBM | {_fmt(d['hbm_peak_mb'])} MB |")
    srv = d.get("serve")
    if srv:
        lines.append(
            f"| serve requests | completed {_fmt(srv['completed'])}, "
            f"rejected {_fmt(srv['rejected'])}, "
            f"timed out {_fmt(srv['timed_out'])} |")
        lines.append(
            f"| serve saturation | queue depth {_fmt(srv['queue_depth'])}, "
            f"slot occupancy {_fmt(srv['slot_occupancy'])} |")
        if srv.get("ttft_p50_ms") is not None:
            lines.append(
                f"| TTFT p50 / p99 | {_fmt(srv['ttft_p50_ms'])} / "
                f"{_fmt(srv['ttft_p99_ms'])} ms |")
        if srv.get("shed") or srv.get("brownout_clamped") \
                or srv.get("brownout_active") or srv.get("replayed") \
                or srv.get("poisoned") or srv.get("journal_errors"):
            flag = " — **overload**" if d.get("overload") else ""
            lines.append(
                f"| serve robustness | shed {_fmt(srv.get('shed'))}, "
                f"clamped {_fmt(srv.get('brownout_clamped'))}, "
                f"replayed {_fmt(srv.get('replayed'))}, poisoned "
                f"{_fmt(srv.get('poisoned'))}, journal errors "
                f"{_fmt(srv.get('journal_errors'))}{flag} |")
        if srv.get("blocks_in_use") is not None \
                or srv.get("prefix_lookups") is not None:
            flag = " — **cache pressure**" if d.get("cache_pressure") else ""
            lines.append(
                f"| serve KV cache | blocks in use "
                f"{_fmt(srv.get('blocks_in_use'))}, prefix hit rate "
                f"{_fmt(srv.get('prefix_hit_rate'))}, preempted "
                f"{_fmt(srv.get('preempted'))}, HBM/req "
                f"{_fmt(srv.get('hbm_per_req_mb'))} MB{flag} |")
        if any(srv.get(k) for k in ("tier_hits_device", "tier_hits_host",
                                    "tier_miss", "host_spilled_blocks")) \
                or (d.get("host_tier") or {}).get("budget_mb"):
            flag = " — **tier incident**" if d.get("tier_incidents") else ""
            lines.append(
                f"| serve cache tiers | device "
                f"{_fmt(srv.get('tier_hits_device'))}, host "
                f"{_fmt(srv.get('tier_hits_host'))}, miss "
                f"{_fmt(srv.get('tier_miss'))}, spilled "
                f"{_fmt(srv.get('host_spilled_blocks'))}, restored "
                f"{_fmt(srv.get('host_restored_blocks'))}, host RAM "
                f"{_fmt(srv.get('host_cache_mb'))} MB{flag} |")
        if srv.get("spec_drafted"):
            flag = " — **low acceptance**" if d.get("spec_incidents") else ""
            lines.append(
                f"| serve speculation | drafted "
                f"{_fmt(srv.get('spec_drafted'))}, accepted "
                f"{_fmt(srv.get('spec_accepted'))}, rejected "
                f"{_fmt(srv.get('spec_rejected'))}, accept rate "
                f"{_fmt(srv.get('accept_rate'))}, "
                f"{_fmt(srv.get('tokens_per_tick'))} tokens/tick{flag} |")
    # counter from the last snapshot when one landed, else the event
    # count — a short churned run with no snapshot still renders the
    # broken invariant
    n_rec = ((srv or {}).get("recompiles")
             or len(d.get("recompile_incidents") or []))
    if n_rec:
        lines.append(
            f"| serve compile | {_fmt(n_rec)} "
            "post-warmup recompile(s) — **broken invariant** |")
    tp = d.get("tickprof")
    if tp and tp.get("dominant"):
        flag = (" — **host-bound**"
                if d.get("host_segment_incidents") else "")
        frac = tp.get("dominant_frac")
        share = (f" {100 * frac:.0f}%"
                 if isinstance(frac, (int, float)) else "")
        # what the steps counted (tick record `c`): live context now,
        # padded prefill work over the same window
        c = tp.get("counters") or {}
        counted = (f"; {_fmt(c.get('kv_tokens'))} KV tokens live, "
                   f"{_fmt(c.get('prefill_tokens'))} prefilled"
                   if c else "")
        for k, v in sorted(c.items()):
            if k.startswith("kv_tokens_"):
                counted += (f" ({_fmt(v)} of them still held by the "
                            f"`{k[len('kv_tokens_'):]}` layers)")
        # how the decode ticks read the cache: the blocks the paged-
        # attention kernel walked, of the entries a gather copies,
        # summed over the layer kinds
        def over_kinds(names):
            return (sum(v or 0 for k, v in c.items() if k.startswith(name))
                    for name in names)

        walked, entries = over_kinds(WALK_COUNTERS)
        if entries:
            counted += (f"; read in place: {_fmt(walked)} "
                        f"of {_fmt(entries)} table entries")
        # how the steps wrote it: whole blocks (a prompt's window that
        # starts on a block) and positions row by row (the ticks' rows,
        # a prompt that starts inside a block), summed over the kinds
        blocks, rows = over_kinds(WRITE_COUNTERS)
        if blocks or rows:
            counted += (f"; written by block: {_fmt(blocks)} block(s), "
                        f"row by row: {_fmt(rows)} position(s)")
        # an expert model: which form of the grouped products its
        # steps' (token, pick) rows went through, over the expert layers
        if any(k in c for k in EXPERT_ROW_COUNTERS):
            by_kernel, by_ragged = (c.get(k) or 0
                                    for k in EXPERT_ROW_COUNTERS)
            counted += (f"; experts: {_fmt(by_kernel)} row(s) through the "
                        f"grouped kernel, {_fmt(by_ragged)} through "
                        f"ragged_dot")
        # which read the steps' prompt windows took, in positions
        tiled, gathered = (c.get(k) or 0 for k in PROMPT_READ_COUNTERS)
        if tiled or gathered:
            counted += (f"; prompt windows: {_fmt(tiled)} position(s) "
                        f"through the tiled kernel, {_fmt(gathered)} "
                        f"through the gather's softmax")
        # a model with recurrent layers: the state rows its ticks read
        # and wrote, of those the state holds, and so the form
        visited, held = (c.get(k) for k in STATE_ROW_COUNTERS)
        if held:
            form = "kernel" if visited < held else "every row"
            counted += (f"; state update: {_fmt(visited)} of {_fmt(held)} "
                        f"rows visited ({form})")
        # an expert model: what its ticks sent to the experts held here
        ex = tp.get("experts") or {}
        if ex:
            counted += (f"; {_fmt(ex['picks_held_per_tick'])} picks a tick "
                        f"on {_fmt(ex['touched_per_tick'])} held experts, "
                        f"the busiest got {_fmt(ex['load_max'])}")
        # a looped model: how often a tick ran its layers over a row
        lp = tp.get("loop") or {}
        if lp:
            counted += (f"; {_fmt(lp['steps'])} passes over "
                        f"{_fmt(lp['layer_passes'] // lp['steps'])} layers "
                        f"a tick ({_fmt(lp['layer_passes'])} cache layers)")
        # a tick whose rows restrict their support (top_k / top_p) runs
        # the sampled path's sorts for every row: worth a sentence
        st = tp.get("sampling_tiers") or {}
        if st.get("sorted"):
            lo, hi = st["restricted_rows"]
            rows = str(lo) if lo == hi else f"{lo}-{hi}"
            counted += (f"; {st['sorted']} of {st['ticks']} ticks sorted "
                        f"the vocabulary for {rows} restricted row(s)")
        lines.append(
            f"| host tick profile | dominant `{tp['dominant']}`"
            f"{_largest_child(tp, tp['dominant'])}{share} over "
            f"{_fmt(tp.get('ticks'))} tick(s){counted}{flag} |")
    rt = d.get("rss_trend")
    if rt:
        flag = " — **climbing**" if d.get("rss_warning") else ""
        lines.append(
            f"| host RSS | {_fmt(rt['first_mb'])} → {_fmt(rt['last_mb'])}"
            f" MB over {rt['samples']} snapshot(s){flag} |")
    fl = d.get("flight")
    if fl:
        seg = (f", dominant `{fl['dominant']}`" if fl.get("dominant")
               else "")
        lines.append(
            f"| flight record | last spill at tick "
            f"{_fmt(fl.get('final_tick'))} (reason "
            f"{fl.get('reason')!r}, {_fmt(fl.get('spills'))} spill(s), "
            f"active {_fmt(fl.get('active'))}, queue "
            f"{_fmt(fl.get('queue'))}{seg}) |")
    for row in d.get("slo_alerts") or []:
        flag = " — **FIRING**" if row.get("active") else " (cleared)"
        lines.append(
            f"| SLO alert `{row['alert']}` | {row['metric']} vs target "
            f"{_fmt(row['threshold'])}: raised {row['raised']}x, "
            f"cleared {row['cleared']}x{flag} |")
    for row in d.get("fleet") or []:
        flag = (" — **dead**" if row["state"] == "dead"
                else " — **never beat**" if row["state"] == "no heartbeat"
                else "")
        occ = ""
        if row.get("active") is not None or row.get("queue") is not None:
            occ = (f", active {_fmt(row.get('active'))}, "
                   f"queue {_fmt(row.get('queue'))}")
        ej = (f", {row['ejections']} ejection(s)"
              if row.get("ejections") else "")
        lines.append(
            f"| replica {row['replica']} | {row['state']} "
            f"(phase {row['phase']!r}, step {_fmt(row.get('step'))}, "
            f"pid {_fmt(row.get('pid'))}, attempt "
            f"{_fmt(row.get('attempt'))}{occ}, beat age "
            f"{_fmt(row.get('age_s'))} s{ej}){flag} |")
    for i, row in enumerate(d.get("tenants") or []):
        flag = (" — **offender**"
                if i == 0 and (row["shed"] or row["rejected"]) else "")
        cls = "/".join(row["classes"]) or "?"
        lines.append(
            f"| tenant `{row['tenant']}` | {cls}: "
            f"admitted {row['admitted']}, shed {row['shed']}, "
            f"rejected {row['rejected']}{flag} |")
    for act in d.get("router_actions") or []:
        lines.append(f"| router action | {act} |")
    sim = d.get("sim")
    if sim:
        shape = (f"{_fmt(sim.get('requests'))} req / "
                 f"{_fmt(sim.get('replicas'))} replicas / "
                 f"{_fmt(sim.get('duration_s'))} s, "
                 f"seed {_fmt(sim.get('seed'))}")
        if sim.get("ok") is None:
            verdict_s = "**no verdict** — harness died mid-scenario"
        elif sim["ok"]:
            verdict_s = f"all {sim['checks']} assertion(s) held"
        else:
            verdict_s = (f"**{sim['failed']}/{sim['checks']} "
                         f"assertion(s) FAILED**: "
                         + "; ".join(sim.get("failed_checks") or ()))
        lines.append(
            f"| simulation `{sim['scenario']}` | {shape} — "
            f"{verdict_s} |")
    for row in d.get("fleet_trace") or []:
        if row.get("q") != 99:
            continue
        comps = ", ".join(f"{p} {v:.1f}"
                          for p, v in row["components_ms"].items() if v)
        flag = (" — **incident**" if any(
            row["metric"] in inc
            for inc in d.get("fleet_trace_incidents") or ()) else "")
        lines.append(
            f"| fleet p{row['q']} {row['metric']} | "
            f"{row['value_ms']:.1f} ms across processes: {comps}, "
            f"other {row['other_ms']:.1f} (dominant "
            f"`{row['dominant']}`){flag} |")
    wal = d.get("router_wal")
    if wal:
        lines.append(
            f"| router WAL | {wal['pending']} pending dispatch(es) in "
            f"`{Path(wal['path']).name}`"
            + (" — **owed streams**" if wal.get("incident") else "")
            + " |")
    for row in d.get("tail_attribution") or []:
        comps = ", ".join(f"{p} {v:.1f}"
                          for p, v in row["components_ms"].items() if v)
        flag = (" — **incident**"
                if row["q"] == 99 and row["metric"] in
                (d.get("tail_incident_metrics") or ()) else "")
        lines.append(
            f"| {row['metric']} p{row['q']} attribution | "
            f"{row['value_ms']:.1f} ms = {comps}, other "
            f"{row['other_ms']:.1f} (dominant: {row['dominant']})"
            f"{flag} |")
    hb = d.get("heartbeat")
    if hb:
        occ = ""
        if hb.get("active") is not None or hb.get("queue") is not None:
            occ = (f", active {_fmt(hb.get('active'))}, "
                   f"queue {_fmt(hb.get('queue'))}")
        lines.append(
            f"| heartbeat | phase {hb['phase']!r}, step {_fmt(hb['step'])}, "
            f"pid {hb['pid']}, {hb['beats']} beats, "
            f"age {_fmt(hb['age_s'])} s{occ} |"
        )
    else:
        lines.append("| heartbeat | none for this run |")
    if d.get("events"):
        ev = ", ".join(f"{k}×{v}" for k, v in sorted(d["events"].items()))
        lines.append(f"| events | {ev} |")
    if d.get("health_events"):
        lines += ["", "**Health events:**", ""]
        for h in d["health_events"]:
            lines.append(f"- step {h['step']}: `{h['anomaly']}` "
                         f"value={h['value']} → {h['action']}")
    return "\n".join(lines) + "\n"


EXIT_BY_VERDICT = {"healthy": 0, "running": 0,
                   "failed": 1, "crashed": 1, "hung": 1, "stalled": 1,
                   "diverged": 1,
                   "empty": 2}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="hyperion obs doctor",
        description="classify a run (healthy/failed/crashed/hung/"
                    "stalled/diverged) from its telemetry stream + "
                    "heartbeat",
    )
    p.add_argument("target", help="run directory (containing "
                                  "telemetry.jsonl) or a telemetry.jsonl")
    p.add_argument("--run", default=None,
                   help="run id to diagnose (default: last in stream)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--stale-s", type=float, default=STALE_S,
                   help="heartbeat age beyond which a non-terminal run "
                        "counts as hung")
    p.add_argument("--now", type=float, default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    tele, _ = locate(args.target)
    if not tele.exists():
        print(f"no telemetry stream at {tele}", file=sys.stderr)
        return 2
    d = diagnose(args.target, run=args.run, now=args.now,
                 stale_s=args.stale_s)
    if args.json:
        print(json.dumps(d, indent=2, default=str))
    else:
        print(render_markdown(d), end="")
    return EXIT_BY_VERDICT.get(d["verdict"], 2)


if __name__ == "__main__":
    raise SystemExit(main())
