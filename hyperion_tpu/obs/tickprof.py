"""Host-tick profiler + crash flight recorder — where a tick's host
time goes, and what the last ticks looked like when a process died.

On the chip most of a tick is the DEVICE's (PERF.md section 5 has the
newest breakdown), and the profiler's trace says where that goes. The
HOST side — queue pops, draft building, block-table uploads,
the accept loop, journal fsyncs, client sink writes, SLO evaluation —
is where a slow disk, a slow client or a long queue shows, and this
module is the one way to time it:

  * `TickProfiler` — a bounded ring of per-tick segment records. The
    engine runs a step inside `with prof.tick(n):` and each stretch of
    it inside `with prof.seg("admit"):`; a segment adds its host-clock
    seconds to the step's record AND is a span on the profiler's clock
    (`serve.step/admit`, a `jax.profiler.TraceAnnotation`) while a
    device trace is taken, so the same names appear in both.
    `snapshot(window_s)` rolls the last-N-seconds into per-segment
    totals/fractions plus the DOMINANT segment, riding the exposition
    payload so `obs top` can show each row's hot segment and `obs
    doctor` can name it when tokens/s degrades ("journal owns 61% of
    tick time — slow disk"). The same roll-up says how many of the
    window's decode ticks ran each tier of `sample_token_slots`
    (`sampling_tiers`, from the records' `sampling_rows` /
    `restricted_rows` counters), what an expert model's ticks
    routed to the experts held here (`experts`), and how much of the
    block tables the ticks' reads touched (`kv_blocks_walked` of
    `kv_table_entries`, and a windowed layer kind's under the same
    names with `_<kind>` behind), and how the steps wrote the pools
    (`kv_blocks_written` whole blocks, `kv_rows_written` positions
    row by row), and which form of the grouped products an expert
    model's steps sent their rows through (`expert_rows_kernel`,
    `expert_rows_ragged`), and which read the steps' prompt windows
    took (`prompt_positions_tiled`, `prompt_positions_gather`), and
    the state rows a recurrent model's ticks updated, of those it holds
    (`state_rows_visited`, `state_rows_held`). The
    profiler's own flight account
    (FLIGHT_COUNTERS: how much of each step a program was out, what
    second fetches and the collector cost it) is in every record's `c`
    and, summed over the window, under `inflight`.
  * `FlightRecorder` — the post-mortem half. The tick ring's tail plus
    recent notable events spill periodically (and on SIGTERM / fatal
    exception) to `flight.json` next to the heartbeat, atomically, so
    even a watchdog SIGKILL leaves the last spill on disk. The FIRST
    eligible spill fires immediately — a replica chaos-killed at tick
    2 still leaves evidence. `obs doctor` cites the record's final
    ticks in its crashed/hung verdicts.

Both are host-only (no jax import: the engine hands the profiler its
annotator, `utils/profiling.annotate`) and null-safe: a recorder built
with `path=None` accepts every call and writes nothing, the same
contract as the null tracer/heartbeat.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
import weakref
from collections import deque
from pathlib import Path

FLIGHT_SCHEMA = 1
FLIGHT_NAME = "flight.json"

# the segment vocabulary (SERVING.md "Profiling and post-mortems"):
# every recorded tick carries a subset of these keys, seconds each.
# "other" is derived at snapshot time (total minus named segments) so
# unattributed host time is visible instead of silently vanishing.
SEGMENTS = ("queue_pop", "admit", "chunk", "ensure", "count", "draft",
            "bt_upload", "device", "accept", "journal", "sink", "slo")
# A key `parent/child` is a CHILD: a stretch inside the segment `parent`
# (`device/dispatch`, `admit/fetch`), recorded beside it and left out of
# every sum over segments — its seconds are already in its parent's. A
# child has children the same way (`admit/blocks/lookup`,
# `device/fetch/tokens`): any key with a separator is out of every sum.
CHILD_SEP = "/"
# Two leaf names mean something to the profiler itself: a child named
# `dispatch` hands a program to the device and the child named `fetch`
# that follows waits for its results, one child of its own per array in
# the order it waits. The first of those holds the program's run time;
# every later one waits for a program that has already ended.
DISPATCH, FETCH = "dispatch", "fetch"
_ARRAY = "array"        # the role of a `x/fetch/<array>` child (`_role`)
# the span of a collection that ran inside a step (`generation=n`)
GC_KEY = "gc"
# What the profiler itself puts into every record's `c`, whole
# microseconds: the step's wall; the part of it during which a program of
# the step had been dispatched and its last array was not yet on the
# host (`step_us - inflight_us`: the chip had nothing of ours queued);
# the gross seconds of every fetch child after its program's first (a
# lower bound on what a second round trip to the device costs); the
# collector's seconds inside the step.
FLIGHT_COUNTERS = ("step_us", "inflight_us", "fetch_after_ready_us", "gc_us")
# the span of a whole step in a profiler trace; a segment's span is
# `serve.step/<key>`
STEP_SPAN = "serve.step"
# the tick record's walk counters (serve/engine.py `_count_walk`): the
# full layer kind's under these names, a windowed kind's with `_<kind>`
# behind; flows, summed over a window's ticks
WALK_COUNTERS = ("kv_blocks_walked", "kv_table_entries")
# and its write counters (`Engine._count_write`), named the same way:
# the whole blocks a step's prefill or chunk put into a kind's pool a
# layer through `paged_kv_write`'s block path, and the positions that
# went row by row (the tick's live rows, a prompt that starts inside a
# block, a bucket under a block)
WRITE_COUNTERS = ("kv_blocks_written", "kv_rows_written")
# an expert model's steps (`Engine._count_experts`): the (token, pick)
# rows a step's tick, chunk and prefills sent through the grouped-matmul
# kernel and through `ragged_dot`, summed over the expert layers, as
# `ops.moe.select_grouped_impl` chose for each call's shape
EXPERT_ROW_COUNTERS = ("expert_rows_kernel", "expert_rows_ragged")
# a step's prefills and chunk (`Engine._count_prompt`): the positions of
# their windows, bucket padding included, that read the gathered chain
# through the tiled kernel and through the gather's one-shot softmax, as
# `models.llama.select_paged_attn_impl` chose for each window's width
PROMPT_READ_COUNTERS = ("prompt_positions_tiled", "prompt_positions_gather")
# a model with recurrent layers (`Engine._count_state`): the state rows
# (rows x recurrent layers) a step's decode tick read and wrote, as
# `models.solar_open2.select_state_update_impl` chose its form, and
# those the state holds (slots x recurrent layers)
STATE_ROW_COUNTERS = ("state_rows_visited", "state_rows_held")


def _role(key: str) -> str | None:
    """What a key's last two names say of a child: `x/dispatch`,
    `x/fetch`, or an array's wait `x/fetch/<array>`."""
    parent, _, leaf = key.rpartition(CHILD_SEP)
    if not parent:
        return None
    if leaf in (DISPATCH, FETCH):
        return leaf
    return _ARRAY if parent.endswith(CHILD_SEP + FETCH) else None


class _Seg:
    """One timed stretch of a step, a context manager (`TickProfiler.seg`).
    After exit `gross` is its wall seconds and `s` those seconds net of
    the segments that ran inside it."""

    __slots__ = ("s", "gross", "_prof", "_key", "_span", "_t0", "_inner",
                 "_record", "_role")

    def __init__(self, prof, key, span, record, role):
        self._prof, self._key, self._span = prof, key, span
        self._record = record
        self._role = role if record else None
        self._inner = 0.0
        self.s = self.gross = 0.0

    def __enter__(self):
        prof = self._prof
        if self._span is not None:
            self._span.__enter__()
        if self._record:
            prof._open.append(self)
        self._t0 = prof._clock()
        if self._role == DISPATCH and prof._flight_t0 is None:
            prof._flight_t0 = self._t0
        elif self._role == FETCH:
            prof._arrays = 0
        return self

    def __exit__(self, *exc):
        prof = self._prof
        now = prof._clock()
        self.gross = now - self._t0
        self.s = max(0.0, self.gross - self._inner)
        if self._role == FETCH and prof._flight_t0 is not None:
            prof._inflight_s += now - prof._flight_t0
            prof._flight_t0 = None
        elif self._role == _ARRAY:
            if prof._arrays and prof._flight_t0 is not None:
                prof._after_ready_s += self.gross
            prof._arrays += 1
        if self._record:
            prof._open.pop()
            cur = prof._cur
            cur[self._key] = cur.get(self._key, 0.0) + self.s
            if CHILD_SEP not in self._key:
                # a segment of its own (journal inside accept, bt_upload
                # inside device): its seconds leave everything around it
                for outer in prof._open:
                    outer._inner += self.s
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class _Tick:
    """One step being profiled (`TickProfiler.tick`): on a clean exit its
    record is filed under the number it was opened with, the same number
    its span carries; a step that raises leaves no record."""

    __slots__ = ("_prof", "_span", "_t0", "_tick", "_counters")

    def __init__(self, prof, tick, span):
        self._prof, self._tick, self._span = prof, tick, span
        self._counters = None

    def count(self, **counters) -> None:
        """What the step counted (the record's `c`)."""
        self._counters = counters

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        prof = self._prof
        prof._cur, prof._open = {}, []
        prof._flight_t0, prof._arrays = None, 0
        prof._inflight_s = prof._after_ready_s = prof._gc_s = 0.0
        prof._thread = threading.get_ident()
        self._t0 = prof._clock()
        return self

    def __exit__(self, exc_type, *exc):
        prof = self._prof
        total = prof._clock() - self._t0
        cur, prof._cur, prof._open = prof._cur, None, []
        # the span ends where `total_s` does: filing the record is not
        # the step's
        if self._span is not None:
            self._span.__exit__(exc_type, *exc)
        if exc_type is None:
            prof.record(self._tick, cur, total, {
                **(self._counters or {}),
                **dict(zip(FLIGHT_COUNTERS, (round(1e6 * x) for x in (
                    total, prof._inflight_s, prof._after_ready_s,
                    prof._gc_s))))})
        return False


class TickProfiler:
    """Bounded ring of per-tick host-segment records.

    The writer (the engine thread) appends one dict per step; readers
    (the exposition thread, the flight recorder) take list() copies of
    the deque — append/copy on a deque are safe under the GIL, so no
    lock sits on the hot path. `tick()` and recording `seg()`s belong to
    that one writer thread.

    `annotate(name, **args)` returns the context manager that puts a
    span on the profiler's clock (`utils/profiling.annotate`); None
    keeps the module free of jax and the segments free of spans."""

    def __init__(self, capacity: int = 256, wall=time.time,
                 clock=time.monotonic, annotate=None):
        self._ring: deque[dict] = deque(maxlen=max(8, int(capacity)))
        self._wall = wall
        self._clock = clock
        self._annotate = annotate
        self._cur: dict | None = None   # the open tick's seconds by key
        self._open: list[_Seg] = []     # its open segments, outermost first
        # the open tick's flight account (FLIGHT_COUNTERS), in seconds
        self._flight_t0: float | None = None    # a program is out since
        self._arrays = 0            # arrays the open fetch has waited for
        self._inflight_s = self._after_ready_s = self._gc_s = 0.0
        self._thread: int | None = None         # the open tick's thread
        # by key, the span's name and the key's role (`_role`)
        self._known: dict[str, tuple[str, str | None]] = {}
        self.ticks_recorded = 0

    def tick(self, n: int) -> _Tick:
        """`with prof.tick(n) as tk:` around one step: the span
        `serve.step` with `tick=n`, and on exit the record of tick `n`:
        the step's wall as `total_s`, the segments timed inside, and
        what `tk.count(...)` was given beside the profiler's own
        FLIGHT_COUNTERS."""
        ann = self._annotate
        return _Tick(self, n, ann(STEP_SPAN, tick=n) if ann else None)

    def seg(self, key: str, record: bool = True, **args) -> _Seg:
        """`with prof.seg("admit"):` times one stretch of the open step
        under `key` and holds the span `serve.step/<key>` (with `args`
        as its arguments) open meanwhile. A segment that runs inside
        another is netted out of it (journal and sink writes inside
        `accept`, the table upload inside `device`); a child
        (`device/fetch`, its own `device/fetch/tokens`) is not, and a
        child named `dispatch` or `fetch` feeds the step's flight
        account (FLIGHT_COUNTERS). Outside a step, or with `record`
        false (a caller that may be on another thread than the
        step's), it is a span and a stopwatch and touches no record."""
        known = self._known.get(key)
        if known is None:
            known = self._known[key] = (f"{STEP_SPAN}/{key}", _role(key))
        ann = self._annotate
        return _Seg(self, key, ann(known[0], **args) if ann else None,
                    record and self._cur is not None, known[1])

    def watch_collector(self) -> None:
        """From now on a collection that runs inside a step, on the
        step's thread, is the span `serve.step/gc` (`generation=n`) and
        its seconds are the record's `gc_us`; the segment it interrupted
        keeps them too. The hook in `gc.callbacks` holds this profiler
        weakly and leaves the list when the profiler goes."""
        _CollectorWatch(self)

    def record(self, tick: int, segments: dict, total_s: float,
               counters: dict | None = None) -> None:
        """One step's breakdown: `segments` maps SEGMENTS names (and
        `parent/child` keys) to host seconds (absent = 0), `total_s` is
        the whole step's wall, `counters` what the step counted."""
        self.ticks_recorded += 1
        rec = {
            "tick": int(tick),
            "t_wall": self._wall(),
            "total_s": float(total_s),
            "s": {k: round(float(v), 6) for k, v in segments.items() if v},
        }
        if counters:
            rec["c"] = dict(counters)
        self._ring.append(rec)

    def tail(self, n: int = 32) -> list[dict]:
        """The most recent <= n records (flight-record payload)."""
        items = list(self._ring)
        return items[-n:]

    def snapshot(self, window_s: float = 60.0,
                 now: float | None = None) -> dict:
        """Windowed roll-up: per-segment seconds + fraction of the
        summed step wall, and the dominant segment. Fractions are of
        TOTAL step time, so "device 0.92" reads directly as "92% of
        tick wall went to the device dispatch+wait"."""
        now = self._wall() if now is None else now
        cut = now - window_s
        recs = [r for r in self._ring if r["t_wall"] >= cut]
        total = sum(r["total_s"] for r in recs)
        sums: dict[str, float] = {}
        children: dict[str, float] = {}
        for r in recs:
            for k, v in r["s"].items():
                into = children if CHILD_SEP in k else sums
                into[k] = into.get(k, 0.0) + v
        named = sum(sums.values())
        if total > named:
            sums["other"] = total - named
        segs = {
            k: {"s": round(v, 6),
                "frac": round(v / total, 4) if total > 0 else 0.0}
            for k, v in sorted(sums.items(), key=lambda kv: -kv[1])
        }
        dominant = next(iter(segs), None)
        out = {
            "ticks": len(recs),
            "window_s": window_s,
            "total_s": round(total, 6),
            "segments": segs,
            "dominant": dominant,
            "dominant_frac": segs[dominant]["frac"] if dominant else None,
        }
        if children:
            # seconds inside their parents' (never part of a sum above)
            out["children"] = {k: round(v, 6) for k, v in sorted(
                children.items(), key=lambda kv: -kv[1])}
        if recs and "c" in recs[-1]:
            # a level reads as of the newest step, a flow over the window
            out["counters"] = {
                "kv_tokens": recs[-1]["c"].get("kv_tokens"),
                # positions a windowed layer kind still holds of them
                **{k: v for k, v in recs[-1]["c"].items()
                   if k.startswith("kv_tokens_")},
                "prefill_tokens": sum(r.get("c", {}).get("prefill_tokens", 0)
                                      for r in recs)}
            # of the table entries the window's decode ticks could have
            # gathered a layer, the blocks the paged-attention kernel
            # walked instead (0: the ticks gathered), by layer kind
            # and how the window's steps wrote it: by block, by row;
            # an expert model: its steps' rows by the products' form;
            # the prompt windows' positions by the read they took;
            # a recurrent model: the state rows its ticks visited
            for key in sorted({k for r in recs for k in r.get("c", {})
                               if k.startswith(
                                   WALK_COUNTERS + WRITE_COUNTERS
                                   + EXPERT_ROW_COUNTERS
                                   + PROMPT_READ_COUNTERS
                                   + STATE_ROW_COUNTERS)}):
                out["counters"][key] = sum(
                    r.get("c", {}).get(key, 0) for r in recs)
        if recs:
            # where the window's step time went as the chip sees it:
            # microseconds of steps, of those with a program out, of
            # second fetches, of collections
            out["inflight"] = {k: sum(r.get("c", {}).get(k, 0) for r in recs)
                               for k in FLIGHT_COUNTERS}
        # an expert model's decode ticks (serve/engine.py
        # `_expert_counters`): picks that landed on the experts held
        # here and held experts touched, a tick (both summed over the
        # expert layers), and the busiest expert's tokens in any tick
        picks = [r["c"] for r in recs if "expert_picks_held" in r.get("c", {})]
        if picks:
            out["experts"] = {
                "ticks": len(picks),
                "picks_held_per_tick": round(sum(
                    c["expert_picks_held"] for c in picks) / len(picks), 3),
                "touched_per_tick": round(sum(
                    c["experts_touched"] for c in picks) / len(picks), 3),
                "load_max": max(c["expert_load_max"] for c in picks)}
        # a looped model's decode ticks (serve/engine.py): the steps a
        # tick ran every row through its layers, and the cache layers
        # it wrote and read (steps x layers), as of the newest tick
        loops = [r["c"] for r in recs if "loop_steps" in r.get("c", {})]
        if loops:
            out["loop"] = {"ticks": len(loops),
                           "steps": loops[-1]["loop_steps"],
                           "layer_passes": loops[-1]["layer_passes"]}
        # which tier of `sample_token_slots` each decode tick of the
        # window ran, from what its rows asked for (a step with no
        # `device` segment ran no tick)
        asked = [(r["c"]["sampling_rows"], r["c"].get("restricted_rows", 0))
                 for r in recs
                 if "device" in r["s"] and "sampling_rows" in r.get("c", {})]
        if asked:
            restricted = [k for _, k in asked if k]
            out["sampling_tiers"] = {
                "ticks": len(asked),
                "greedy": sum(not n for n, _ in asked),
                "drawn": sum(bool(n) and not k for n, k in asked),
                "sorted": len(restricted),
                "restricted_rows": ([min(restricted), max(restricted)]
                                    if restricted else None)}
        return out


class _CollectorWatch:
    """One entry of `gc.callbacks` (`TickProfiler.watch_collector`): two
    clock reads a collection, and no work when none runs. Safe wherever
    a collection starts: it reads the profiler's open step and adds to
    one float of it, and touches no segment."""

    __slots__ = ("_prof", "_t0", "_span", "_callbacks")

    def __init__(self, prof: TickProfiler):
        # the callback of the reference runs when the profiler is freed,
        # never while the interpreter walks `gc.callbacks`
        self._prof = weakref.ref(prof, self._gone)
        self._t0 = self._span = None
        # the list itself: at interpreter exit the module `gc` is gone
        self._callbacks = gc.callbacks
        self._callbacks.append(self)

    def _gone(self, _ref) -> None:
        if self in self._callbacks:
            self._callbacks.remove(self)

    def __call__(self, phase: str, info: dict) -> None:
        prof = self._prof()
        if prof is None:
            return
        if phase == "start":
            if prof._cur is None or prof._thread != threading.get_ident():
                return
            if prof._annotate is not None:
                self._span = prof._annotate(
                    f"{STEP_SPAN}/{GC_KEY}", generation=info["generation"])
                self._span.__enter__()
            self._t0 = prof._clock()
            return
        # each on its own: an interrupt may have cut `start` short
        if self._t0 is not None:
            prof._gc_s += prof._clock() - self._t0
            self._t0 = None
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


class FlightRecorder:
    """Atomic spiller of the last-known engine state to `flight.json`.

    The caller (the engine) owns WHAT goes in a spill — the recorder
    owns WHEN (first eligible tick, then every `spill_every`) and HOW
    (same-directory temp + `os.replace`, the heartbeat's torn-write
    discipline). `note()` collects sparse notable events (recompiles,
    journal errors, chaos fires) into a bounded deque that rides every
    spill."""

    def __init__(self, path: str | Path | None, *, run: str | None = None,
                 spill_every: int = 16, max_events: int = 64,
                 wall=time.time):
        self.path = Path(path) if path else None
        self.enabled = self.path is not None
        self.run = run
        self.spill_every = max(1, int(spill_every))
        self.events: deque[dict] = deque(maxlen=max(4, int(max_events)))
        self._wall = wall
        self._last_spill_tick: int | None = None
        self.spills = 0

    def note(self, name: str, **attrs) -> None:
        """Record a notable moment (rides the next spill)."""
        if not self.enabled:
            return
        self.events.append({"name": name, "t_wall": self._wall(), **attrs})

    def due(self, tick: int) -> bool:
        """Periodic-spill policy: the FIRST call is always due (a crash
        at tick 2 must still find evidence on disk), then every
        `spill_every` ticks."""
        if not self.enabled:
            return False
        return (self._last_spill_tick is None
                or tick - self._last_spill_tick >= self.spill_every)

    def spill(self, reason: str, payload: dict | None = None, *,
              tick: int | None = None) -> None:
        """Unconditional atomic write. `payload` is the caller's state
        dump (tick ring tail, compile ledger, memory); the recorder
        adds the envelope + its event buffer. IO failure degrades the
        recorder, never the process — same posture as the heartbeat."""
        if not self.enabled:
            return
        self.spills += 1
        if tick is not None:
            self._last_spill_tick = tick
        rec = {
            "v": FLIGHT_SCHEMA,
            "run": self.run,
            "pid": os.getpid(),
            "t_wall": self._wall(),
            "reason": reason,
            "tick": tick,
            "spills": self.spills,
            "events": list(self.events),
            **(payload or {}),
        }
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            tmp = self.path.with_name(self.path.name + ".tmp")
            tmp.write_text(json.dumps(rec, separators=(",", ":"),
                                      default=repr))
            os.replace(tmp, self.path)
        except OSError:
            self.enabled = False


def null_flight_recorder() -> FlightRecorder:
    return FlightRecorder(None)


def read_flight(path: str | Path) -> dict | None:
    """Tolerant flight-record reader (doctor's side): None when missing
    or unparseable — the atomic writer makes a torn file near
    impossible, but a reader must never crash on one."""
    try:
        rec = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


def flight_final_tick(flight: dict) -> int | None:
    """The last tick the record saw — the spill's own tick stamp, or
    the newest ring entry's."""
    t = flight.get("tick")
    if isinstance(t, int):
        return t
    ticks = flight.get("ticks")
    if isinstance(ticks, list) and ticks:
        last = ticks[-1]
        if isinstance(last, dict) and isinstance(last.get("tick"), int):
            return last["tick"]
    return None
