"""From a profiler trace (`.xplane.pb`) to the device's busy and idle
time, the operations that took most of it, the time of each compiled
program, and the idle gaps named by what the host was doing. The one
place where a trace becomes numbers.

Read with `jax.profiler.ProfileData`: planes hold lines, lines hold
events with a name, a start and a duration in nanoseconds on one clock
for host and device. On a TPU plane (`/device:TPU:<n>`) the line
`XLA Ops` has one event per operation run and `XLA Modules` one per run
of a compiled program. The host plane's lines are threads; the
benchmark's own spans (`jax.profiler.TraceAnnotation`) are the events
there whose names start with `bench.`."""

from __future__ import annotations

import contextlib
import re
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(trace_dir):
    """The profiler on, around one `bench.window` span: host spans are
    recorded, Python calls are not (they would slow the loop traced)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def span(on: bool, name: str):
    """A host span in the trace while one is taken, else nothing."""
    import jax

    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def reduce_dir(trace_dir, **kw) -> dict | None:
    return reduce(load(newest_xplane(trace_dir)), **kw)


def newest_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _events(line) -> list[tuple[str, float, float]]:
    """(name, start, end) in seconds."""
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def op_kind(name: str) -> str:
    """A device event is named by its whole HLO line: `%copy.311 =
    bf16[48,128,16,8,128]{...} copy(...)`. Kept: the name without its
    number and the shape of the result, so that the sixteen layers'
    copies of one operation count as one."""
    lhs, eq, rest = name.partition(" = ")
    if not eq:
        return name[:80]
    shape = rest.split("{")[0].split(" ")[0].lstrip("(")
    return f"{re.sub(r'[.][0-9]+$', '', lhs)} {shape}"[:80]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_spans(profile) -> list[tuple[str, float, float]]:
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda ev: ev[1])


def reduce(profile, top: int = 10, step_segments=None) -> dict | None:
    """None where no operation ran on a device. `step_segments` maps
    the index k of a span `bench.step:k` to that step's host segments in
    the order they ran, [(name, seconds)]: a gap inside such a span is
    then named `bench.step/<segment>` by its offset from the span's start.

    busy_s     union of the device's operation intervals inside the
               window, averaged over the devices that ran any
    window_s   the span `bench.window` where the host recorded one,
               else first operation start to last operation end
    device_ops the `top` kinds of operation (`op_kind`) by summed time
    modules    {program name: [seconds of each run inside the window]}
    idle_gaps  the `top` (name, seconds) of idle time by the `bench.*`
               span the host had open meanwhile, or `(no span)`"""
    per_device = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        if ops:
            mods = _events(lines[MODULES_LINE]) \
                if MODULES_LINE in lines else []
            per_device.append((ops, mods))
    if not per_device:
        return None
    spans = host_spans(profile)
    win = next(((s, e) for n, s, e in spans if n == WINDOW_SPAN), None)
    if win is None:
        win = (min(s for ops, _ in per_device for _, s, _ in ops),
               max(e for ops, _ in per_device for _, _, e in ops))
    lo, hi = win
    n = len(per_device)
    intervals = _named_intervals(spans, step_segments or {})
    busy = 0.0
    op_s: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    gap_s: dict[str, float] = {}
    for ops, mods in per_device:
        merged = _clip(union((s, e) for _, s, e in ops), lo, hi)
        busy += sum(e - s for s, e in merged) / n
        for name, s, e in ops:
            if e > lo and s < hi:
                kind = op_kind(name)
                op_s[kind] = op_s.get(kind, 0.0) \
                    + (min(e, hi) - max(s, lo)) / n
        for name, s, e in mods:
            if s >= lo and e <= hi:
                modules.setdefault(name, []).append(e - s)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        _attribute([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a], intervals, gap_s, 1 / n)

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy, "window_s": hi - lo, "devices": n,
            "device_ops": ranked(op_s), "modules": modules,
            "idle_gaps": ranked(gap_s)}


def _named_intervals(spans, step_segments: dict) -> list:
    """The host's spans as (name, start, end), a `bench.step:k` span cut
    into its segments where `step_segments` has them. The benchmark's
    spans follow one another and do not nest, but for the window's."""
    out = []
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        # `bench.step:17` -> `bench.step`: the index tells runs apart,
        # the table wants the kind
        kind, _, index = name.partition(":")
        at = s
        for seg, dur in (step_segments.get(int(index), [])
                         if index.isdigit() else []):
            end = min(at + dur, e)
            if end > at:
                out.append((f"{kind}/{seg}", at, end))
            at = end
        if e > at:
            out.append((f"{kind}/other" if at > s else kind, at, e))
    return sorted(out, key=lambda x: x[1])


def _attribute(gaps, intervals, acc: dict, weight: float) -> None:
    """Adds each gap's seconds to the names of the intervals it
    overlaps; both lists are sorted and disjoint in themselves."""
    i = 0
    for a, b in gaps:
        while i < len(intervals) and intervals[i][2] <= a:
            i += 1
        j, left = i, b - a
        while j < len(intervals) and intervals[j][1] < b:
            name, s, e = intervals[j]
            over = min(b, e) - max(a, s)
            if over > 0:
                acc[name] = acc.get(name, 0.0) + over * weight
                left -= over
            j += 1
        if left > 1e-12:
            acc["(no span)"] = acc.get("(no span)", 0.0) + left * weight
