"""Flight recorder heartbeat — one atomically-replaced JSON file per run.

The failure mode it exists for is runs that die *silently*: a hung
backend init looks exactly like a slow compile from the outside, and a
watcher's only recourse is killing and re-running stages on a timer. The heartbeat closes that gap: every
entry point (trainers, the server, the generation CLI) rewrites a small
`heartbeat.json` next to its telemetry stream — run id, pid, process
index, last step, phase, monotonic + wall timestamps — so an external
reader can distinguish

  * progressing  — heartbeat fresh, step advancing
  * slow         — heartbeat fresh, step advancing slowly (do NOT kill)
  * hung         — heartbeat stale: the host loop itself stopped
  * done         — terminal phase written before exit

without parsing the full JSONL stream. On a crash or preemption the
last heartbeat plus the telemetry tail IS the post-mortem; `obs doctor`
reads both.

Write discipline: the file is replaced atomically (`os.replace` of a
same-directory temp file) so a reader can never observe a torn write,
and writes are rate-limited (every N steps OR every `interval_s`
seconds, whichever fires first) so a 1 ms step loop does not turn into
an fsync storm. A beat is one small `json.dumps` + rename on the HOST —
no device interaction whatsoever, so it can never add a sync to the
step loop.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

SCHEMA_VERSION = 1

# env knob mirroring trace.ENV_VAR: unset -> ride the tracer's policy,
# "0" -> force off, anything else -> a path to write the heartbeat to.
ENV_VAR = "HYPERION_HEARTBEAT"


def host_rss_mb() -> float | None:
    """This process's peak resident set in MB, from `getrusage` (stdlib,
    no psutil). Linux reports `ru_maxrss` in KB; it is a HIGH-WATER
    mark, so the value never decreases — trend readers (doctor's
    host-leak warning) look for a peak that is STILL RISING late in a
    run, which a plateaued process stops doing. None where the platform
    has no usable counter."""
    try:
        import resource
        kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return round(kb / 1024.0, 1) if kb > 0 else None
    except Exception:  # noqa: BLE001 — absent evidence, not a crash
        return None


class Heartbeat:
    """Rate-limited atomic writer of one run's heartbeat file.

    A disabled heartbeat (`path=None`) accepts every call and writes
    nothing — call sites carry zero conditionals, same contract as the
    null tracer."""

    def __init__(
        self,
        path: str | Path | None,
        *,
        run: str | None = None,
        proc: int = 0,
        every: int = 25,
        interval_s: float = 15.0,
        enabled: bool = True,
        static: dict | None = None,
        clock=time.monotonic,
        wall=time.time,
    ):
        self.path = Path(path) if path else None
        self.enabled = bool(enabled and self.path is not None)
        self.run = run or f"run_{int(wall())}"
        self.proc = proc
        # fields stamped on EVERY beat (e.g. the supervisor restart
        # attempt) — per-call extras override on collision
        self.static = dict(static) if static else {}
        self.every = max(1, int(every))
        self.interval_s = interval_s
        self._clock = clock
        self._wall = wall
        self._beats = 0
        self._last_step: int | None = None
        self._last_phase: str | None = None
        self._last_t: float | None = None

    @classmethod
    def for_tracer(cls, tracer, every: int = 25, **kw) -> "Heartbeat":
        """Heartbeat riding the tracer's policy: enabled iff the tracer
        writes, living as `heartbeat.json` next to its stream. ENV_VAR
        overrides: "0" forces off, a path redirects."""
        val = os.environ.get(ENV_VAR, "")
        if val == "0":
            return null_heartbeat()
        if val not in ("", "1"):
            return cls(val, run=tracer.run, proc=tracer.proc,
                       every=every, **kw)
        if not tracer.enabled:
            return null_heartbeat()
        return cls(tracer.path.parent / "heartbeat.json",
                   run=tracer.run, proc=tracer.proc, every=every, **kw)

    def beat(self, step: int | None = None, phase: str | None = None,
             **extra) -> None:
        """Maybe-write: fires on a phase change, on the first call, when
        `step` advanced >= `every` since the last write, or when
        `interval_s` wall seconds elapsed (slow steps must not make a
        live run look hung)."""
        if not self.enabled:
            return
        due = (
            self._last_t is None
            or phase != self._last_phase
            or (step is not None
                and (self._last_step is None
                     or step - self._last_step >= self.every))
            or self._clock() - self._last_t >= self.interval_s
        )
        if due:
            self.pulse(step=step, phase=phase, **extra)

    def pulse(self, step: int | None = None, phase: str | None = None,
              **extra) -> None:
        """Unconditional write (phase transitions, final state)."""
        if not self.enabled:
            return
        self._beats += 1
        self._last_step = step if step is not None else self._last_step
        self._last_phase = phase
        self._last_t = self._clock()
        rec = {
            "v": SCHEMA_VERSION,
            # explicit schema stamp for the live plane's readers (obs
            # top, the router's replica state machine): payload growth
            # bumps nothing — new fields ride along and old readers
            # ignore them (read_heartbeat returns the whole dict, no
            # field whitelist) — while a future INCOMPATIBLE change
            # bumps this and readers can branch on it
            "schema": SCHEMA_VERSION,
            "run": self.run,
            "pid": os.getpid(),
            "proc": self.proc,
            "step": self._last_step,
            "phase": phase,
            "t_wall": self._wall(),
            "t_mono": self._last_t,
            "beats": self._beats,
            # host memory on every beat: the heartbeat is what outlives
            # a kill, so the last-known RSS is post-mortem evidence
            "rss_mb": host_rss_mb(),
            **self.static,
            **extra,
        }
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            tmp = self.path.with_name(self.path.name + ".tmp")
            tmp.write_text(json.dumps(rec, separators=(",", ":"),
                                      default=repr))
            os.replace(tmp, self.path)  # atomic: readers never see a torn file
        except OSError:
            # a full disk must degrade the flight recorder, not the run
            self.enabled = False

    @property
    def last_phase(self) -> str | None:
        return self._last_phase

    @property
    def last_step(self) -> int | None:
        return self._last_step

    def close(self, phase: str = "done", **extra) -> None:
        """Terminal pulse — readers distinguish 'exited cleanly' from
        'stopped beating'."""
        self.pulse(step=self._last_step, phase=phase, **extra)


def null_heartbeat() -> Heartbeat:
    return Heartbeat(None, enabled=False)


def read_heartbeat(path: str | Path) -> dict | None:
    """Parse a heartbeat file; None when missing or unreadable (an
    atomic writer means a torn file should be impossible, but a reader
    must never crash on one anyway). Unknown fields are preserved, not
    rejected: the live plane grows the payload (alerts, occupancy,
    replica tags) and an older reader must keep working on a newer
    writer's file — the schema-contract tests pin this tolerance."""
    try:
        rec = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


def heartbeat_age_s(hb: dict, now: float | None = None) -> float | None:
    """Wall-clock seconds since the last beat (None if the record has no
    usable timestamp). Wall time is comparable across processes, which
    monotonic time is not."""
    t = hb.get("t_wall")
    if not isinstance(t, (int, float)):
        return None
    return (time.time() if now is None else now) - float(t)
