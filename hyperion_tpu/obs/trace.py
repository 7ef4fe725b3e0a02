"""Span/event tracer — one JSONL line per record, hot-loop safe.

Design constraints, in order:

1. **No host sync in the step loop.** Spans time the HOST side with
   `time.perf_counter`; under JAX async dispatch a per-step span is
   dispatch latency, not device time. Honest device timing comes from
   `Span.fence(tree)` — a `utils.timing.host_fence` host fetch — used
   exactly where the trainers already fenced (epoch boundaries), never
   per step. On the simulated-CPU test mesh the epoch loop fences every
   step anyway, so step spans are honest there (which is what the smoke
   acceptance run measures).
2. **Append-only JSONL.** Multiple runs share one `<workdir>/
   telemetry.jsonl`; every record carries the run id, so readers filter
   by run. Writes are buffered and flushed at snapshot/close, not per
   line — a step span costs one dict + one buffered `write`.
3. **Null-safe.** A disabled tracer (no path, or non-primary process)
   accepts every call and writes nothing, so call sites carry zero
   conditionals.

Record schema (one JSON object per line):
    {"v": 1, "kind": "span"|"event"|"snapshot",
     "name": str, "run": str, "proc": int, "step": int|null,
     "t_wall": float,  # unix seconds at record END (span) / emit (event)
     "t_mono": float,  # monotonic seconds at span START / event emit
     "dur_ms": float,  # spans only
     "path": "epoch/train_step",  # spans only: nesting path
     ...attrs flattened at top level (names must not collide with the
     reserved keys above; reserved wins)}
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable

SCHEMA_VERSION = 1
_RESERVED = ("v", "kind", "name", "run", "proc", "step", "t_wall", "t_mono",
             "dur_ms", "path")

# env knob shared by every entry point: unset/"" -> each entry point's
# own default (trainers: on, under base_dir; bench/infer: off), "0" ->
# force off, "1" -> the entry point's default path, anything else -> a
# JSONL path to append to.
ENV_VAR = "HYPERION_TELEMETRY"


class Span:
    """Handle yielded by `Tracer.span`; mutate attrs or request a fence
    before exit. After exit, `dur_ms`/`dur_s` hold the measured time."""

    __slots__ = ("name", "attrs", "_fence_tree", "_t0", "dur_ms")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._fence_tree = None
        self._t0 = 0.0
        self.dur_ms: float | None = None

    @property
    def dur_s(self) -> float | None:
        return None if self.dur_ms is None else self.dur_ms / 1e3

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def fence(self, tree: Any) -> "Span":
        """Fence this span's end on a host fetch of `tree` (see
        `utils.timing.host_fence`) — device-honest timing. Only for
        epoch-scale spans: it is a host sync."""
        self._fence_tree = tree
        return self


class _SpanCtx:
    __slots__ = ("_tracer", "_span", "_step")

    def __init__(self, tracer: "Tracer", span: Span, step):
        self._tracer = tracer
        self._span = span
        self._step = step

    def __enter__(self) -> Span:
        t = self._tracer
        self._span._t0 = t._clock()
        t._stack.append(self._span.name)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        t = self._tracer
        sp = self._span
        if sp._fence_tree is not None:
            from hyperion_tpu.utils.timing import host_fence

            host_fence(sp._fence_tree)
        sp.dur_ms = (t._clock() - sp._t0) * 1e3
        path = "/".join(t._stack)
        t._stack.pop()
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        t._emit({
            "kind": "span", "name": sp.name, "path": path,
            "t_mono": sp._t0, "dur_ms": round(sp.dur_ms, 3),
            **_clean(sp.attrs),
        }, step=self._step)
        return False


def _clean(attrs: dict) -> dict:
    return {k: v for k, v in attrs.items() if k not in _RESERVED}


class Tracer:
    """JSONL span/event writer bound to one (path, run, process).

    `clock`/`wall` are injectable for tests (fake clocks). A tracer
    with `path=None` or `enabled=False` is a null tracer: every call
    no-ops, spans still time themselves (dur_ms is set) so callers can
    read durations regardless."""

    def __init__(
        self,
        path: str | Path | None,
        *,
        run: str | None = None,
        proc: int | None = None,
        enabled: bool = True,
        clock: Callable[[], float] = time.perf_counter,
        wall: Callable[[], float] = time.time,
    ):
        self.path = Path(path) if path else None
        self.run = run or f"run_{int(wall())}"
        self.enabled = bool(enabled and self.path is not None)
        if proc is None:
            # only an ENABLED tracer may pay the dist lookup: the dist
            # module imports jax, and on a multi-host box process_index
            # can initialize the backend — a null tracer inside a parent
            # that never touches jax by design (`--supervise`, `hyperion
            # route`) must stay import-free.
            proc = 0
            if self.enabled:
                try:
                    from hyperion_tpu.runtime import dist

                    proc = dist.process_index()
                except Exception:  # noqa: BLE001 — never kill a run
                    proc = 0
        self.proc = proc
        self.step: int | None = None
        self._clock = clock
        self._wall = wall
        self._stack: list[str] = []
        self._f = None
        self._lock = threading.Lock()

    # -------------------------------------------------------- plumbing

    def _file(self):
        if self._f is None:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._f = self.path.open("a", encoding="utf-8")
        return self._f

    def _emit(self, rec: dict, step: int | None = None) -> None:
        if not self.enabled:
            return
        full = {
            "v": SCHEMA_VERSION,
            "run": self.run,
            "proc": self.proc,
            "step": self.step if step is None else step,
            "t_wall": self._wall(),
            **rec,
        }
        line = json.dumps(full, separators=(",", ":"), default=_json_default)
        with self._lock:
            self._file().write(line + "\n")
            # events are rare lifecycle marks whose whole value is
            # surviving a killed process; flush them eagerly. Hot-loop span records stay buffered.
            if rec.get("kind") == "event":
                self._f.flush()

    # ------------------------------------------------------------- api

    def set_step(self, step: int | None) -> None:
        """Default `step` stamped on subsequent records (spans/events can
        still override per call)."""
        self.step = step

    def span(self, name: str, step: int | None = None, **attrs) -> _SpanCtx:
        """`with tracer.span("fwd") as sp:` — nestable; the record lands
        at exit with dur_ms and the full nesting path."""
        return _SpanCtx(self, Span(name, attrs), step)

    def event(self, name: str, step: int | None = None, **attrs) -> None:
        """Point-in-time record (lifecycle marks, decisions, errors)."""
        self._emit({
            "kind": "event", "name": name, "t_mono": self._clock(),
            **_clean(attrs),
        }, step=step)

    def snapshot(self, registry, step: int | None = None, **attrs) -> None:
        """Emit a `MetricsRegistry.snapshot()` as one record."""
        self._emit({
            "kind": "snapshot", "name": "metrics", "t_mono": self._clock(),
            "metrics": registry.snapshot(), **_clean(attrs),
        }, step=step)
        self.flush()

    def flush(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _json_default(o):
    """Telemetry must never crash a run on an exotic attr value: numpy
    scalars become floats, everything else its repr."""
    try:
        return float(o)
    except Exception:  # noqa: BLE001
        return repr(o)


def null_tracer() -> Tracer:
    return Tracer(None, enabled=False)


def from_env(
    default_path: str | Path | None = None,
    *,
    run: str | None = None,
    proc: int | None = None,
    enabled_by_default: bool = False,
) -> Tracer:
    """Entry-point policy in one place (see `ENV_VAR` above).

    Trainers call with `enabled_by_default=True` and their workdir path;
    bench/infer CLIs call with their default path but leave telemetry
    opt-in, so test suites and ad-hoc invocations don't litter the repo.
    `proc` is forwarded verbatim: pass 0 from processes that must not
    import the jax-loading dist module just to learn their rank.
    """
    val = os.environ.get(ENV_VAR, "")
    if val == "0":
        return null_tracer()
    if val in ("", "1"):
        if val == "" and not enabled_by_default:
            return null_tracer()
        if default_path is None:
            return null_tracer()
        return Tracer(default_path, run=run, proc=proc)
    return Tracer(val, run=run, proc=proc)
