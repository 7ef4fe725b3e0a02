"""Profiler trace capture — the idiomatic upgrade over wall-clock timers.

Reference profiling is wall-clock brackets + memory counters only
(SURVEY §5.1: `benchmarking.py:37-49`, memory probes throughout; no
torch.profiler/rocprof integration anywhere). The TPU-native upgrade is
`jax.profiler` trace capture: XLA emits per-op device timelines, and the
program's own host spans ride the same file on the same clock.

This module starts and stops traces and opens spans; nothing else does:

  * `capture()` wraps any code region; trainers expose it via
    `--profile-dir` so one flag turns a training epoch into a trace.
    `on_demand_trace()` is the live server's (`obs profile`).
  * `annotate(name, **args)` is a span on the profiler's clock
    (`jax.profiler.TraceAnnotation`): the engine's step segments
    (`serve.step/*`, through `obs/tickprof.py`) and the trainer's loop
    (`train.next_batch`, `train.dispatch`, `train.fetch` inside a
    `StepTraceAnnotation("train")`) are written with it. With no trace
    being taken a span costs well under a microsecond.

`obs/xprof.py` turns the trace these leave into numbers (`obs profile
--summarize`).
"""

from __future__ import annotations

import contextlib
import threading
from pathlib import Path

import jax


@contextlib.contextmanager
def capture(trace_dir: str | Path | None):
    """Context manager: profile the enclosed region into `trace_dir`
    (TensorBoard/XProf format). None = no-op, so call sites can pass the
    config value straight through."""
    if not trace_dir:
        yield None
        return
    trace_dir = Path(trace_dir)
    trace_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir), profiler_options=_options())
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def _options():
    """Host spans yes, Python calls no: the Python tracer slows the very
    loop being traced and swamps the host lines with frames."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def annotate(name: str, **args):
    """Named span on the profiler's clock; `args` ride it as the span's
    arguments (`tick=17`, `bucket=2048`)."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_annotate(name: str, step_num: int):
    """A whole training step, as the profiler's tools know steps."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def annotated(iterable, name: str):
    """`iterable`, each `next()` of it inside the span `name`: how a
    `for` loop's wait for its next item becomes visible in a trace."""
    it = iter(iterable)
    while True:
        with annotate(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


# on-demand tracing (the `obs profile` control verb): one trace at a
# time per process — jax.profiler is a process-global singleton
_TRACE_LOCK = threading.Lock()
_TRACE_ACTIVE: list[str] = []


def on_demand_trace(out_dir: str | Path, seconds: float) -> dict:
    """Bracket `jax.profiler.start_trace`/`stop_trace` around a timer:
    the caller (a live serving loop answering its exposition socket)
    returns immediately with `{"status": "started"}` while a daemon
    timer stops the trace after `seconds`. Degrades to a structured
    answer — never an exception — on backends without profiler support
    (`"unsupported"`) or when a trace is already running (`"busy"`)."""
    seconds = max(0.1, min(float(seconds), 600.0))
    out = str(out_dir)
    with _TRACE_LOCK:
        if _TRACE_ACTIVE:
            return {"status": "busy", "dir": _TRACE_ACTIVE[0]}
        try:
            Path(out).mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(out, profiler_options=_options())
        except Exception as e:  # noqa: BLE001 — answer, don't raise
            return {"status": "unsupported", "error": repr(e)[:300]}
        _TRACE_ACTIVE.append(out)

    def _stop():
        # outside the lock: stopping writes the trace out, which takes
        # seconds on a busy host, and a request that arrives meanwhile
        # is answered `busy` at once (the trace is still the active
        # one) instead of waiting on the lock past its client's timeout
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        with _TRACE_LOCK:
            _TRACE_ACTIVE.clear()

    t = threading.Timer(seconds, _stop)
    t.daemon = True
    t.start()
    return {"status": "started", "dir": out, "seconds": seconds}
