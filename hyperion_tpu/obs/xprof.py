"""From a profiler trace to numbers: `obs profile --summarize`.

`utils/profiling.py` starts and stops traces; this module is the
program's one reader of what they leave (`<dir>/plugins/profile/<time>/
*.xplane.pb`). It answers an operator's three questions of a trace:

  * how much of the window was the device busy, and idle;
  * where did the busy time go, by compiled program and by the scope
    the program gave the operation (`jit__tick_impl | Llama/layer_*/
    attn/kv_read`, `jit_train_step | bwd | ...`): flax names a scope per
    module and the code adds `jax.named_scope`s where no module
    boundary is (`sampling`, `kv_read`, `optimizer`), so the rows keep
    their names when a change renames every fusion;
  * what was the host doing while the device idled, by the innermost of
    the program's own spans open meanwhile (`serve.step/admit/fetch`,
    `train.fetch`: `obs/tickprof.py`, `train/trainer.py`), which ride
    the same file on the same clock.

Events are read with `jax.profiler.ProfileData`: planes hold lines,
lines hold events with a name, a start, a duration and their own stats.
Where an operation's scope comes from depends on the backend:

  * TPU: the plane's event METADATA carries `tf_op` (the operation's
    `op_name`, what XProf's own tools group by) and the compiler's
    `flops` and `bytes_accessed`. `ProfileData` does not expose metadata
    stats, so `_op_metadata` reads those tables straight off the file's
    protobuf wire format (measured on a v5e, jax 0.9.0: PERF.md).
  * CPU: an operation's event carries `hlo_module` and `hlo_op` and no
    `op_name`: everything reads `unscoped` unless the caller hands
    `summarize` a map {module: {instruction: op_name}} (the tier-1 tests
    take one from the executables' text; no TPU trace needs it).

An operation the compiler made itself has no `op_name` on any backend;
it takes the scope its neighbours share (`_from_neighbours`).

An operation with no `op_name`, or none below its program's root, is
`unscoped`, and that row is always reported: JAX leaves `op_name` out of
the compile cache's key, so an executable taken from a cache that an
older checkout filled shows its OLD scopes, and a large `unscoped` (or
a scope the code no longer has) is how a reader sees it.

No jax import at module level: only `load` needs it.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import struct
from pathlib import Path

from hyperion_tpu.obs.tickprof import DISPATCH, FETCH

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
UNSCOPED = "unscoped"
NO_SPAN = "(no span)"
LONGEST_GAPS = 20
# the spans with the most idle seconds that get the runtime's own events
# listed under them, and how many events each
MEANWHILE_SPANS = MEANWHILE = 5
# A span that hands a program to the device ends in DISPATCH, the span
# that waits for its results in FETCH (`serve.step/device/dispatch`,
# `train.dispatch`): the names `obs/tickprof.py` gives those children.
# the program's own host spans start with one of these: the engine's
# step segments and the trainer's loop. Everything else on a host line
# is the runtime's (`PjitFunction(..)`, `PjRtCpuExecutable::Execute`).
SPAN_PREFIXES = ("serve.", "train")
# name-stack wrappers that say how an operation was derived, not where
# in the program it sits; `transpose(` marks the backward pass
_TRANSFORMS = ("transpose", "jvp", "vmap", "checkpoint", "remat",
               "custom_jvp", "custom_vjp", "shard_map")
# name-stack components that control flow and calls put in: a scan's
# body is not a place in the program
_CONTROL = frozenset(("while", "body", "cond", "closed_call", "core_call",
                      "custom_jvp_call", "custom_vjp_call", "checkpoint",
                      "remat", "rematted_computation"))


# ------------------------------------------------------------ scopes

def _split(op_name: str) -> list[str]:
    """`a/b(c/d)/e` -> [`a`, `b(c/d)`, `e`]: slashes inside parentheses
    belong to the component."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def _unwrap(comp: str) -> str:
    """`transpose(jvp(TransformerLM))` -> `TransformerLM`; a component
    that wraps nothing but a call (`jit(_take)`, `vmap(jit(_gumbel))`,
    `vmap()`) -> ``."""
    while comp.endswith(")"):
        head, _, rest = comp.partition("(")
        if head not in _TRANSFORMS:
            return ""
        comp = rest[:-1]
    return comp


def scope_of(op_name: str | None) -> tuple[str, str]:
    """(pass, scope) of an operation's `op_name`: pass is `bwd` for the
    transposed half of a gradient, else ``; scope is the path below the
    program's root with call and transform wrappers, control flow's
    own components and the primitive's name stripped, `layer_<n>`
    folded to `layer_*`, or `unscoped`.

    `jit(_tick_impl)/Llama/layer_3/attn/kv_read/gather` ->
    (``, `Llama/layer_*/attn/kv_read`)."""
    if not op_name:
        return "", UNSCOPED
    parts = _split(op_name.rstrip(":"))
    if len(parts) < 2 or "(" not in parts[0]:
        # `reduce_sum`, `st['lengths']`: nothing below a program's root
        return "", UNSCOPED
    bwd = any("transpose(" in p for p in parts)
    body = parts[1:]
    if "(" not in body[-1]:
        body = body[:-1]            # the primitive's own name
    scope: list[str] = []
    for comp in body:
        comp = _unwrap(comp) if "(" in comp else comp
        if comp in _CONTROL or "->" in comp:    # `bhqk,bkhd->bqhd`: einsum
            continue
        comp = re.sub(r"_\d+$", "_*", comp)
        if comp and (not scope or scope[-1] != comp):
            scope.append(comp)
    return ("bwd" if bwd else ""), ("/".join(scope) or UNSCOPED)


def _instruction(event_name: str) -> str:
    """A TPU event is named by its whole HLO line, `%fusion.3 = bf16[..]
    fusion(..)`; a CPU one by the instruction's name alone."""
    return event_name.partition(" = ")[0].lstrip("%")


_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _operands(event_name: str) -> list[str]:
    """Names of the instructions an HLO line reads (`calls=%..` and
    `to_apply=%..` name computations, not operands)."""
    return _OPERAND.findall(event_name.partition(" = ")[2])


def _from_neighbours(event_name: str, *ways, depth: int = 3):
    """(pass, scope) for an operation the compiler made and left without
    an `op_name` (the scatter it turns top-p's un-sort into, a layout
    copy, the prefetch of a weight): the longest scope path that every
    neighbour which has one lies under. Each of `ways` maps an HLO line
    to its neighbours as [(op_name or None, HLO line or None)]: what it
    reads first, and only if none of that has a scope, what reads it;
    unnamed neighbours are looked through `depth` deep. None where no
    neighbour has a scope or they share none: it stays `unscoped`."""
    for neighbours in ways:
        found: list[tuple[str, list[str]]] = []
        level = [event_name]
        for _ in range(depth):
            below = []
            for line in level:
                for op_name, text in neighbours(line):
                    pas, scope = scope_of(op_name)
                    if scope != UNSCOPED:
                        found.append((pas, scope.split("/")))
                    elif not op_name and text:
                        below.append(text)
            if found or not below:
                break
            level = below
        if found:
            break
    else:
        return None
    shared = found[0][1]
    for _, path in found[1:]:
        k = 0
        while k < min(len(shared), len(path)) and shared[k] == path[k]:
            k += 1
        shared = shared[:k]
    if not shared:
        return None
    passes = {pas for pas, _ in found}
    return (passes.pop() if len(passes) == 1 else ""), "/".join(shared)


# ------------------------------------------- the file's metadata tables

def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, never descended into here."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield key >> 3, wt, val


def _op_metadata(path) -> dict[str, dict[str, dict]]:
    """{plane name: {event name: {tf_op, flops, bytes_accessed,
    program_id}}} from an
    `.xplane.pb`'s event-metadata tables (tensorflow/tsl `xplane.proto`:
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5,
    both maps of key=1 to value=2; XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .uint64=3, .int64=4, .str=5, .ref=7;
    XStatMetadata.name=2). Lines and events are skipped, not parsed:
    `ProfileData` reads those."""
    want = ("tf_op", "flops", "bytes_accessed", "program_id")
    out: dict[str, dict[str, dict]] = {}
    for f, wt, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1 or wt != 2:
            continue
        name, stat_names, metas = "", {}, []
        for pf, pwt, val in _fields(plane):
            if pf == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pf in (4, 5) and pwt == 2:
                key, msg = 0, None
                for ef, _, ev in _fields(val):
                    if ef == 1:
                        key = ev
                    elif ef == 2:
                        msg = ev
                if msg is None:
                    continue
                if pf == 4:
                    metas.append(msg)
                else:
                    stat_names[key] = next(
                        (bytes(v).decode("utf-8", "replace")
                         for mf, _, v in _fields(msg) if mf == 2), "")
        if not name.startswith("/device:"):
            continue
        table: dict[str, dict] = {}
        for msg in metas:
            ev_name, stats = "", {}
            for mf, mwt, val in _fields(msg):
                if mf == 2:
                    ev_name = bytes(val).decode("utf-8", "replace")
                elif mf == 5 and mwt == 2:
                    sid, sval = 0, None
                    for sf, swt, sv in _fields(val):
                        if sf == 1:
                            sid = sv
                        elif sf in (3, 4):
                            sval = sv
                        elif sf == 2:
                            sval = struct.unpack("<d", bytes(sv))[0]
                        elif sf == 5:
                            sval = bytes(sv).decode("utf-8", "replace")
                        elif sf == 7:
                            sval = stat_names.get(sv, "")
                    if stat_names.get(sid) in want and sval is not None:
                        stats[stat_names[sid]] = sval
            if ev_name:     # stat-less ones too: their lines name operands
                table[ev_name] = stats
        if table:
            out[name] = table
    return out


# ------------------------------------------------------------- reading

def xplane_path(trace) -> Path:
    """The `.xplane.pb` itself, or the newest under a trace directory."""
    p = Path(trace)
    if p.is_file():
        return p
    found = sorted(p.glob("plugins/profile/*/*.xplane.pb")) \
        or sorted(p.glob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


class _Op:
    __slots__ = ("name", "start", "end", "self_s", "leaf", "module",
                 "instr")

    def __init__(self, name, start, end, module=None):
        self.name, self.start, self.end = name, start, end
        self.self_s, self.leaf = end - start, True
        self.module, self.instr = module, _instruction(name)


def _self_times(ops: list[_Op]) -> None:
    """One line's events nest (a `while` holds its body's operations):
    each keeps the seconds none of its children cover."""
    stack: list[_Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_s -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)


def _seconds(e):
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def _program(module_event_name: str) -> str:
    """`jit__tick_impl(5274983520953125)` -> `jit__tick_impl`."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def _devices(profile) -> list[dict]:
    """[{name, ops, runs, peaks}] for each device that ran an operation.
    `runs` is [(program, start, end)] of the compiled programs' runs."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = [_Op(e.name, *_seconds(e)) for e in lines[OPS_LINE].events]
        if not ops:
            continue
        _self_times(ops)
        runs = sorted(((_program(e.name), *_seconds(e))
                       for e in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else [])),
                      key=lambda r: r[1])
        starts = [r[1] for r in runs]
        for op in ops:
            k = bisect.bisect_right(starts, op.start) - 1
            # an operation's program is the run it started in (a run's
            # last operations may end a few nanoseconds after it)
            if k >= 0 and op.start < runs[k][2]:
                op.module = runs[k][0]
        stats = dict(plane.stats)
        peaks = None
        if "peak_teraflops_per_second" in stats:
            peaks = {
                "flops_per_s": 1e12 * float(
                    stats["peak_teraflops_per_second"]),
                "bytes_per_s": 1e9 * float(
                    stats.get("peak_hbm_bw_gigabytes_per_second", 0) or 0),
                "source": "trace"}
        out.append({"name": plane.name, "ops": ops, "runs": runs,
                    "peaks": peaks})
    if out:
        return out
    # the CPU backend has no device plane: its operations are events of
    # the host's worker threads that carry `hlo_op` and `hlo_module`
    by_dev: dict[str, dict] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ops = []
            for e in line.events:
                st = dict(e.stats)
                if "hlo_op" in st and "hlo_module" in st:
                    op = _Op(str(st["hlo_op"]), *_seconds(e),
                             module=str(st["hlo_module"]))
                    ops.append((op, st.get("device_ordinal", 0),
                                st.get("run_id")))
            _self_times([o for o, _, _ in ops])
            for op, ordinal, run in ops:
                dev = by_dev.setdefault(
                    f"{plane.name}#{ordinal}",
                    {"name": f"{plane.name}#{ordinal}", "ops": [],
                     "by_run": {}, "peaks": None})
                dev["ops"].append(op)
                lo, hi = dev["by_run"].get((op.module, run),
                                           (op.start, op.end))
                dev["by_run"][(op.module, run)] = (min(lo, op.start),
                                                   max(hi, op.end))
    for dev in by_dev.values():
        dev["runs"] = sorted(((m, s, e) for (m, _), (s, e)
                              in dev.pop("by_run").items()),
                             key=lambda r: r[1])
    return list(by_dev.values())


class _Span:
    __slots__ = ("name", "start", "end", "args", "parent")

    def __init__(self, name, start, end, args):
        self.name, self.start, self.end = name, start, end
        self.args, self.parent = args, None

    def all_args(self) -> dict:
        """Its own arguments over those of the spans around it:
        `bucket` of `serve.step/admit/fetch`, `tick` of `serve.step`."""
        chain, sp = [], self
        while sp is not None:
            chain.append(sp.args)
            sp = sp.parent
        out: dict = {}
        for args in reversed(chain):
            out.update(args)
        return out


def _host_lines(profile):
    """((plane name, line's place, line name), line) of every host
    thread's line: two threads may share a name."""
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for at, line in enumerate(plane.lines):
                yield (plane.name, at, line.name), line


def _program_spans(profile) -> dict[tuple, list[_Span]]:
    """The program's spans by the host line that holds them, each list
    sorted by start (a span's children start no earlier and end no
    later)."""
    threads = {}
    for key, line in _host_lines(profile):
        # `_`-led stats are the profiler's own bookkeeping
        spans = [_Span(e.name, *_seconds(e),
                       {k: v for k, v in e.stats if not k.startswith("_")})
                 for e in line.events if e.name.startswith(SPAN_PREFIXES)]
        if spans:
            threads[key] = sorted(spans, key=lambda s: (s.start, -s.end))
    return threads


def _runtime_events(profile, but: tuple | None) -> list[tuple]:
    """(start, end, name, line name) of what the runtime's own threads
    recorded: every host line but the loop's (`but`), the program's
    spans on them left out. Sorted by start."""
    return sorted(
        (*_seconds(e), e.name, key[2])
        for key, line in _host_lines(profile) if key != but
        for e in line.events if not e.name.startswith(SPAN_PREFIXES))


def _overlaps(intervals, events) -> dict[tuple, float]:
    """{(event name, line name): seconds} of `events`
    (`_runtime_events`) inside the sorted, disjoint `intervals`."""
    starts = [a for a, _ in intervals]
    ends = [b for _, b in intervals]
    held: dict[tuple, float] = {}
    for s, e, name, line in events:
        k = bisect.bisect_right(ends, s)
        over = 0.0
        while k < len(starts) and starts[k] < e:
            over += min(e, ends[k]) - max(s, starts[k])
            k += 1
        if over > 0:
            held[(name, line)] = held.get((name, line), 0.0) + over
    return held


def _program_lag(spans: list[_Span], devices: list[dict]) -> list[dict]:
    """How long a program waited to start and how long its results
    waited to be read, by (program, dispatch span): for each `*dispatch`
    span of the loop's thread and the `*fetch` span that follows it, the
    longest run of a compiled program inside the pair (an upload's
    one-operation `convert_element_type` may run there too). Launch lag
    is the run's start minus the dispatch span's start; fetch lag the
    fetch span's end minus the run's end."""
    fetches: dict[str, list[_Span]] = {}
    for sp in spans:
        if sp.name.endswith(FETCH):
            fetches.setdefault(sp.name, []).append(sp)
    starts_of = {name: [sp.start for sp in sps]
                 for name, sps in fetches.items()}
    by_device = [(d["runs"], [r[1] for r in d["runs"]]) for d in devices]
    lags: dict[tuple, list[tuple[float, float]]] = {}
    for sp in spans:
        if not sp.name.endswith(DISPATCH):
            continue
        partner = sp.name[:-len(DISPATCH)] + FETCH
        k = bisect.bisect_left(starts_of.get(partner, []), sp.end)
        if k >= len(fetches.get(partner, [])):
            continue
        fetch = fetches[partner][k]
        for runs, run_starts in by_device:
            inside = [r for r in runs[bisect.bisect_left(
                run_starts, sp.start):bisect.bisect_right(
                run_starts, fetch.end)] if r[2] <= fetch.end]
            if inside:
                prog, start, end = max(inside, key=lambda r: r[2] - r[1])
                lags.setdefault((prog, sp.name), []).append(
                    (start - sp.start, fetch.end - end))
    n = len(devices)
    return sorted((
        {"program": prog, "dispatch": name, "n": len(v),
         "launch_median_s": statistics.median(a for a, _ in v),
         "launch_total_s": sum(a for a, _ in v) / n,
         "fetch_median_s": statistics.median(b for _, b in v),
         "fetch_total_s": sum(b for _, b in v) / n}
        for (prog, name), v in lags.items()),
        key=lambda r: -(r["launch_total_s"] + r["fetch_total_s"]))


def _innermost(spans: list[_Span]) -> list[tuple[float, float, _Span]]:
    """Disjoint (start, end, span) pieces in time order: at each moment
    the span that opened last and has not closed. For ONE thread's spans:
    only those nest. Sets `parent`."""
    out, stack = [], []
    cursor = float("-inf")

    def close(until):
        nonlocal cursor
        while stack and stack[-1].end <= until:
            top = stack.pop()
            if top.end > cursor:
                out.append((max(cursor, top.start), top.end, top))
                cursor = top.end

    for sp in spans:
        close(sp.start)
        if stack:
            sp.parent = stack[-1]
            if sp.start > cursor:
                out.append((max(cursor, stack[-1].start), sp.start,
                            stack[-1]))
        cursor = max(cursor, sp.start)
        stack.append(sp)
    close(float("inf"))
    return out


def _loop_seconds(spans: list[_Span]) -> float:
    """Seconds one thread spent inside the loop's own spans (`serve.step`,
    `train`, `train.fetch`): a segment's span (`serve.step/sink`) can be
    another thread's, the step's cannot."""
    return sum(e - s for s, e in _union(
        (sp.start, sp.end) for sp in spans if "/" not in sp.name))


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


# ----------------------------------------------------------- summarize

def summarize(trace, peaks: dict | None = None, *, window=None,
              scopes: dict | None = None) -> dict:
    """The numbers of one trace.

    trace   a trace directory or an `.xplane.pb`
    peaks   {"flops_per_s", "bytes_per_s"} of one device: with it (or
            with the peaks a TPU plane states itself) every row that has
            FLOPs or bytes gets its share of the roofline
    window  (start, end) in the trace's seconds; default: first
            operation's start to last operation's end
    scopes  {module: {instruction: op_name}} for a backend whose events
            carry no `op_name` (CPU)

    Seconds of the whole trace are means over the devices that ran an
    operation. Idle seconds go to the spans of ONE host thread, the one
    that runs the loop (most seconds inside `serve.step` or `train*`):
    spans that other threads open (a reader thread's `serve.step/sink`)
    are counted under `host_spans` and name no gap.
    Returns a dict of plain numbers, lists and strings (see the keys
    below; `to_markdown` renders it). Raises ValueError where no
    operation ran on a device."""
    path = xplane_path(trace)
    meta = _op_metadata(path)
    scopes = scopes or {}
    profile = load(path)
    devices = _devices(profile)
    if not devices:
        raise ValueError("no operation ran on a device in this trace")

    threads = _program_spans(profile)
    spans = [sp for th in threads.values() for sp in th]
    # the thread that runs the loop
    loop = max(threads, key=lambda k: _loop_seconds(threads[k]),
               default=None)
    pieces = _innermost(threads.get(loop, []))
    if window is None:
        window = (min(o.start for d in devices for o in d["ops"]),
                  max(o.end for d in devices for o in d["ops"]))
    lo, hi = window
    n = len(devices)
    if peaks is None:
        peaks = next((d["peaks"] for d in devices if d["peaks"]), None)

    rows: dict[tuple, dict] = {}
    by_source = {"event_stat": 0.0, "given": 0.0, "neighbours": 0.0,
                 "none": 0.0}
    left: dict[tuple, list] = {}
    idle_by: dict[str, float] = {}
    # by span, each device's idle intervals under it
    idle_at: dict[str, list[list]] = {}
    gaps: list[dict] = []
    per_device = []
    modules: dict[str, list[float]] = {}
    busy = 0.0
    for dev in devices:
        table = meta.get(dev["name"], {})
        # an operation's neighbours by name: {(program_id, instruction):
        # (op_name, HLO line)}, and who reads whom (built on first need)
        by_instr = {(st.get("program_id"), _instruction(ev)):
                    (st.get("tf_op"), ev) for ev, st in table.items()}
        readers: dict[tuple, list] = {}

        def reads(line, pid):
            return [by_instr.get((pid, i), (None, None))
                    for i in _operands(line)]

        def read_by(line, pid):
            if not readers:
                for (p, _), named in by_instr.items():
                    for i in _operands(named[1]):
                        readers.setdefault((p, i), []).append(named)
            return readers.get((pid, _instruction(line)), [])

        inherited: dict[str, tuple | None] = {}
        merged = [(max(s, lo), min(e, hi))
                  for s, e in _union((o.start, o.end) for o in dev["ops"])
                  if e > lo and s < hi]
        dev_busy = sum(e - s for s, e in merged)
        busy += dev_busy / n
        per_device.append({"device": dev["name"], "window_s": hi - lo,
                           "busy_s": dev_busy,
                           "idle_s": (hi - lo) - dev_busy})
        for op in dev["ops"]:
            if op.end <= lo or op.start >= hi or op.self_s <= 0:
                continue
            # an operation cut by the window's edge keeps the same share
            # of its own seconds as of its whole
            cut = (min(op.end, hi) - max(op.start, lo)) \
                / max(op.end - op.start, 1e-12)
            sec = op.self_s * cut
            st = table.get(op.name, {})
            op_name, source = st.get("tf_op"), "event_stat"
            if not op_name:
                op_name = scopes.get(op.module or "", {}).get(op.instr)
                source = "given" if op_name else "none"
            pas, scope = scope_of(op_name)
            # no `op_name`, or one the compiler gave a custom call of its
            # own making (`ragged-dot-none:`): no path of the program's
            if scope == UNSCOPED and "/" not in (op_name or ""):
                if op.name not in inherited:    # once, not once a run
                    pid = st.get("program_id")
                    inherited[op.name] = _from_neighbours(
                        op.name, lambda ln: reads(ln, pid),
                        lambda ln: read_by(ln, pid))
                if inherited[op.name]:
                    (pas, scope), source = inherited[op.name], "neighbours"
            by_source[source] += sec / n
            if scope == UNSCOPED:
                seen = left.setdefault((op.module, op.instr), [0.0, 0,
                                                               op.name])
                seen[0] += sec / n
                seen[1] += 1
            row = rows.setdefault((op.module or "(no program)", pas, scope),
                                  {"s": 0.0, "n": 0})
            row["s"] += sec / n
            row["n"] += 1
            if op.leaf:
                for key in ("flops", "bytes_accessed"):
                    if key in st:
                        row[key] = row.get(key, 0) + st[key] * cut / n
        for prog, s, e in dev["runs"]:
            if lo <= (s + e) / 2 <= hi:
                modules.setdefault(prog, []).append(e - s)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        dev_gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
        _attribute(dev_gaps, pieces, idle_by, idle_at, gaps, dev, 1 / n)

    total = sum(r["s"] for r in rows.values()) or 1e-12
    device_rows = []
    for (prog, pas, scope), r in sorted(rows.items(),
                                        key=lambda kv: -kv[1]["s"]):
        row = {"program": prog, "pass": pas, "scope": scope,
               "s": r["s"], "share": r["s"] / total, "ops": r["n"]}
        if "flops" in r:
            row["flops"] = r["flops"]
        if "bytes_accessed" in r:
            row["bytes"] = r["bytes_accessed"]
        if peaks and r["s"] > 0 and ("flops" in r or "bytes_accessed" in r):
            t_f = r.get("flops", 0) / peaks["flops_per_s"]
            t_b = (r.get("bytes_accessed", 0) / peaks["bytes_per_s"]
                   if peaks.get("bytes_per_s") else 0.0)
            row["roofline_pct"] = 100 * max(t_f, t_b) / r["s"]
            row["bound"] = "flops" if t_f >= t_b else "bytes"
        device_rows.append(row)
    unscoped = sum(r["s"] for r in device_rows if r["scope"] == UNSCOPED)
    idle = (hi - lo) - busy
    host: dict[str, list[float]] = {}
    for sp in spans:
        if sp.end > lo and sp.start < hi:
            host.setdefault(sp.name, []).append(sp.end - sp.start)
    gaps.sort(key=lambda g: -g["s"])
    idle_rows = [
        {"span": k, "s": v, "share": v / idle if idle > 0 else 0.0}
        for k, v in sorted(idle_by.items(), key=lambda kv: -kv[1])]
    # what the runtime's own threads were doing in the gaps under the
    # spans that hold most of the idle time
    runtime = _runtime_events(profile, loop) if idle_rows else []
    for row in idle_rows[:MEANWHILE_SPANS]:
        held: dict[tuple, float] = {}
        for intervals in idle_at.get(row["span"], []):
            for key, sec in _overlaps(intervals, runtime).items():
                held[key] = held.get(key, 0.0) + sec / n
        row["meanwhile"] = [
            {"event": name, "thread": line, "s": sec}
            for (name, line), sec in sorted(
                held.items(), key=lambda kv: -kv[1])[:MEANWHILE]]
    return {
        "trace": str(path), "devices": n,
        "window_s": hi - lo, "busy_s": busy, "idle_s": idle,
        "idle_share": idle / (hi - lo) if hi > lo else 0.0,
        "per_device": per_device,
        "peaks": peaks,
        # seconds of device time by where the operation's scope was found
        "scope_source_s": by_source,
        "device_rows": device_rows,
        "unscoped_s": unscoped, "unscoped_share": unscoped / total,
        # what is left without a scope, largest first: the place to put
        # the next `jax.named_scope`, or the sign of a stale executable
        "unscoped_ops": [
            {"program": prog or "(no program)", "op": name[:160],
             "s": sec, "n": cnt}
            for (prog, _), (sec, cnt, name) in sorted(
                left.items(), key=lambda kv: -kv[1][0])[:LONGEST_GAPS]],
        "idle_by_span": idle_rows,
        # launch and fetch lag of each compiled program the loop sent
        "program_lag": _program_lag(threads.get(loop, []), devices),
        "longest_gaps": gaps[:LONGEST_GAPS],
        "host_spans": sorted((
            {"span": k, "n": len(v), "total_s": sum(v),
             "median_s": statistics.median(v), "max_s": max(v)}
            for k, v in host.items()), key=lambda r: -r["total_s"]),
        "modules": sorted((
            {"program": k, "n": len(v), "median_s": statistics.median(v),
             "total_s": sum(v)} for k, v in modules.items()),
            key=lambda r: -r["total_s"]),
    }


def _attribute(dev_gaps, pieces, idle_by, idle_at, gaps, dev,
               weight) -> None:
    """Each idle gap's seconds go to the innermost spans it overlaps
    (both lists sorted and disjoint), and the overlap itself to this
    device's list under the span's name in `idle_at`; the gap itself is
    listed under the span that holds most of it, with the programs
    around it."""
    mine: dict[str, list] = {}
    by_end = sorted(dev["ops"], key=lambda o: o.end)
    by_start = sorted(dev["ops"], key=lambda o: o.start)
    ends = [o.end for o in by_end]
    starts = [o.start for o in by_start]
    i = 0
    for a, b in dev_gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j, left = i, b - a
        best, best_s = None, 0.0
        while j < len(pieces) and pieces[j][0] < b:
            s, e, sp = pieces[j]
            over = min(b, e) - max(a, s)
            if over > 0:
                idle_by[sp.name] = idle_by.get(sp.name, 0.0) + over * weight
                mine.setdefault(sp.name, []).append((max(a, s), min(b, e)))
                left -= over
                if over > best_s:
                    best, best_s = sp, over
            j += 1
        if left > 1e-12:
            idle_by[NO_SPAN] = idle_by.get(NO_SPAN, 0.0) + left * weight
        # the programs of the operations that end and start the gap
        k = bisect.bisect_right(ends, a + 1e-9) - 1
        m = bisect.bisect_left(starts, b - 1e-9)
        gaps.append({
            "s": b - a, "start_s": a, "device": dev["name"],
            "span": best.name if best and best_s >= left else NO_SPAN,
            "args": best.all_args() if best and best_s >= left else {},
            "before": by_end[k].module if k >= 0 else None,
            "after": by_start[m].module if m < len(starts) else None})
    for name, intervals in mine.items():
        idle_at.setdefault(name, []).append(intervals)


def to_markdown(summary: dict, rows: int = 40) -> str:
    """The summary as tables, for a terminal or a notes file."""
    s = summary
    out = [f"window {s['window_s']:.4f} s, busy {s['busy_s']:.4f} s, "
           f"idle {s['idle_s']:.4f} s ({100 * s['idle_share']:.2f} %), "
           f"{s['devices']} device(s)", ""]
    costs = any("roofline_pct" in r for r in s["device_rows"])
    head = "| program | pass | scope | s | % of busy |"
    rule = "|---|---|---|---|---|"
    if costs:
        head += " GFLOP | MB | % of roofline |"
        rule += "---|---|---|"
    out += ["device seconds by program and scope "
            f"(unscoped {100 * s['unscoped_share']:.2f} %):", "",
            head, rule]
    shown = s["device_rows"][:rows]
    if not any(r["scope"] == UNSCOPED for r in shown):
        shown = shown + [r for r in s["device_rows"]
                         if r["scope"] == UNSCOPED][:3]
    for r in shown:
        line = (f"| {r['program']} | {r['pass']} | {r['scope']} | "
                f"{r['s']:.4f} | {100 * r['share']:.2f} |")
        if costs:
            line += (f" {r['flops'] / 1e9:.2f} |" if "flops" in r
                     else " |")
            line += (f" {r['bytes'] / 1e6:.1f} |" if "bytes" in r
                     else " |")
            line += (f" {r['roofline_pct']:.1f} ({r['bound']}) |"
                     if "roofline_pct" in r else " |")
        out.append(line)
    out += ["", "scope found by: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in s["scope_source_s"].items() if v)]
    if s["unscoped_ops"]:
        out += ["", "largest operations left without a scope:", "",
                "| program | s | runs | operation |", "|---|---|---|---|"]
        out += [f"| {u['program']} | {u['s']:.5f} | {u['n']} | "
                f"`{u['op'][:90]}` |" for u in s["unscoped_ops"][:5]]
    out += ["", "idle seconds by the program's innermost span:", "",
            "| span | s | % of idle |", "|---|---|---|"]
    out += [f"| {r['span']} | {r['s']:.4f} | {100 * r['share']:.1f} |"
            for r in s["idle_by_span"][:rows]]
    for r in s["idle_by_span"]:
        if r.get("meanwhile"):
            out += ["", f"the runtime's threads in the gaps under "
                        f"{r['span']} ({r['s']:.4f} s):", "",
                    "| event | thread | s |", "|---|---|---|"]
            out += [f"| {m['event'][:80]} | {m['thread']} | {m['s']:.4f} |"
                    for m in r["meanwhile"]]
    if s["program_lag"]:
        out += ["", "launch lag (a program's start after its dispatch "
                    "span's) and fetch lag (its fetch span's end after "
                    "the program's):", "",
                "| program | dispatch span | n | launch median ms | "
                "launch total s | fetch median ms | fetch total s |",
                "|---|---|---|---|---|---|---|"]
        out += [f"| {r['program']} | {r['dispatch']} | {r['n']} | "
                f"{1e3 * r['launch_median_s']:.3f} | "
                f"{r['launch_total_s']:.4f} | "
                f"{1e3 * r['fetch_median_s']:.3f} | "
                f"{r['fetch_total_s']:.4f} |" for r in s["program_lag"]]
    out += ["", "longest gaps:", "",
            "| ms | span | args | program before | program after |",
            "|---|---|---|---|---|"]
    out += [f"| {1e3 * g['s']:.3f} | {g['span']} | "
            f"{json.dumps(g['args']) if g['args'] else ''} | "
            f"{g['before'] or ''} | {g['after'] or ''} |"
            for g in s["longest_gaps"]]
    out += ["", "host spans:", "",
            "| span | n | total s | median ms | max ms |",
            "|---|---|---|---|---|"]
    out += [f"| {r['span']} | {r['n']} | {r['total_s']:.4f} | "
            f"{1e3 * r['median_s']:.3f} | {1e3 * r['max_s']:.3f} |"
            for r in s["host_spans"][:rows]]
    out += ["", "runs of compiled programs:", "",
            "| program | n | median ms | total s |", "|---|---|---|---|"]
    out += [f"| {r['program']} | {r['n']} | {1e3 * r['median_s']:.3f} | "
            f"{r['total_s']:.4f} |" for r in s["modules"]]
    return "\n".join(out) + "\n"
