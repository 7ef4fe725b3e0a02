"""Run telemetry — span/event tracing, metric gauges, and run summaries.

The reference earned its results by measuring everything (per-phase
fwd/bwd/opt time, peak memory, throughput — SURVEY §5); this subsystem
is that discipline made continuous: every entry point (train, serve,
infer) streams step-level spans and per-epoch metric snapshots to one
append-only JSONL file, and `hyperion obs summarize <telemetry.jsonl>`
turns any run's stream into a markdown report (p50/p99 step time, MFU,
tokens/sec, memory high-water, slowest spans) without re-running under
a profiler.

Producer half (PR 1):
  * `trace`     — nestable spans + point events, one JSONL line each,
                  run-id/step/process-index/monotonic-timestamp on every
                  record; optional `host_fence`-backed device timing at
                  epoch boundaries (never inside the step loop).
  * `registry`  — counters/gauges/histograms with a per-step
                  `snapshot()`, plus built-in helpers for tokens/sec,
                  step-time EMA, device memory, and MFU from compiled
                  `cost_analysis()` FLOPs vs `utils.chips` peaks.
  * `report`    — JSONL -> summary dict -> markdown, and the
                  `obs summarize` CLI subcommand.

Consumer/health half (PR 2 — the stream diagnosing its own runs):
  * `heartbeat` — atomically-replaced `heartbeat.json` flight recorder
                  (run/pid/step/phase/timestamps) so an external watcher
                  can tell hung from slow without parsing the stream.
  * `health`    — in-band `HealthMonitor`: non-finite loss/grads, loss
                  spikes (rolling z-score), grad explosions, step-time
                  stalls; `health` events into the trace + a
                  warn/checkpoint/abort escalation policy. Consumes
                  host floats only — it cannot add a device sync.
  * `doctor`    — `obs doctor <dir>`: classify a run (healthy/crashed/
                  hung/stalled/diverged) from telemetry + heartbeat,
                  with evidence.
  * `diff`      — `obs diff <a> <b>`: percent-delta comparison of two
                  run summaries with a regression threshold.
  * `timeline`  — `obs trace <dir>`: per-request waterfalls
                  reconstructed from the serve path's lifecycle events,
                  Chrome trace-event/Perfetto export, worst-k exemplar
                  requests, and tail-latency attribution (TTFT/e2e at
                  p50/p99 decomposed into queue / block-gate / prefill /
                  decode / preempt-replay / client-write); the doctor's
                  named serving incidents come from the same math.

Live half (PR 10 — the pull-based plane for running fleets):
  * `export`    — one-request exposition socket (`obs.sock` next to the
                  heartbeat): a live process answers with registry
                  counters/gauges, windowed histogram summaries, phase,
                  drain/brownout state, and firing alerts — zero device
                  syncs, host floats only.
  * `slo`       — declarative SLO targets (TTFT p99, reject rate,
                  availability) evaluated with multi-window burn rates
                  (fast 1m / slow 10m) inside the engine/router loops;
                  transitions emit `alert_raised`/`alert_cleared`
                  events, ride heartbeats, and feed doctor/diff.
  * `top`       — `obs top <dir>`: curses-free ANSI fleet dashboard
                  polling the exposition sockets (heartbeat fallback
                  for dead processes); `--once --json` for scripts.

Device half (PR 24 — one plane on the profiler's clock):
  * `tickprof`  — the engine's host-tick profiler: `prof.seg()` times a
                  step's segments into the tick record AND holds them
                  open as spans in a `jax.profiler` trace; the flight
                  recorder spills the last ticks for a post-mortem.
  * `xprof`     — `obs profile --summarize`: a profiler trace to
                  numbers — device seconds by program and scope, idle
                  seconds by the program's own spans, costs and
                  roofline shares where the trace carries them.

Reaction half (PR 3 — `train/supervisor.py` + `checkpoint/integrity.py`):
the doctor's verdicts drive a restart supervisor (crashed/hung ->
restart from the newest verified checkpoint; diverged -> quarantine
first), each relaunch stamps `attempt` into heartbeat + `train_start`
so `doctor` reports restart lineage, and `preempt_signal` events mark
signal latches the instant they happen.
"""

from hyperion_tpu.obs.export import (  # noqa: F401
    MetricsExporter,
    exposition_path,
    read_exposition,
)
from hyperion_tpu.obs.health import (  # noqa: F401
    Anomaly,
    HealthConfig,
    HealthMonitor,
)
from hyperion_tpu.obs.slo import (  # noqa: F401
    SLOMonitor,
    SLOTarget,
    standard_targets,
)
from hyperion_tpu.obs.heartbeat import (  # noqa: F401
    Heartbeat,
    heartbeat_age_s,
    null_heartbeat,
    read_heartbeat,
)
from hyperion_tpu.obs.registry import (  # noqa: F401
    MetricsRegistry,
    compiled_flops,
    mfu_value,
    observe_device_memory,
    observe_input_wait,
    observe_mfu,
    observe_step,
    observe_throughput,
)
from hyperion_tpu.obs.trace import Tracer, from_env, null_tracer  # noqa: F401
