"""Bounded admission queue with backpressure, deadlines, and a
prefill-token budget per scheduling round.

Serving dies two ways at the front door: unbounded queues (every
request accepted, every request slow — the collapse mode) and prefill
monopolies (one 4k-token prompt prefilling while eight interactive
requests' decode ticks wait). Both are queue policy, not engine policy,
so they live here:

  * **Backpressure** — `submit` REJECTS with a machine-readable reason
    (`queue_full`, `too_long`) instead of buffering forever; the
    caller/client sees the rejection immediately and can retry
    elsewhere. Rejecting at admission is the only point where the cost
    of saying no is still zero.
  * **Deadlines** — a request may carry an SLO (`deadline_s`, relative
    to submission). The scheduler drops expired requests at pop time
    (`timed_out`) rather than burning slots decoding answers nobody is
    waiting for.
  * **FIFO with a prefill budget** — `pop_ready` admits in arrival
    order but caps the total prompt tokens admitted per scheduling
    round. Prefill is the only O(prompt) step in the serve loop; the
    budget bounds how long any single round can stall the decode ticks
    of requests already in flight. A prompt larger than the whole
    budget still admits when it reaches the head (alone in its round) —
    bounded delay, never starvation.

The queue is thread-safe: transports (stdin reader thread, socket
handler threads) submit concurrently while the engine loop pops.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from collections import deque
from typing import Any, Callable

import numpy as np

from hyperion_tpu.utils.clock import SYSTEM

_ids = itertools.count()

# SLO classes (the `class` wire field): `interactive` is the latency
# tier — TTFT is the product; `batch` is the throughput tier — it
# absorbs every degradation first (sheds, clamps, preemption) so that
# one hostile batch tenant can never tax an interactive request's tail.
# Unknown class strings normalize to interactive: misspelling a class
# must never silently demote a request to the sheddable tier.
CLASS_INTERACTIVE = "interactive"
CLASS_BATCH = "batch"
SLA_CLASSES = (CLASS_INTERACTIVE, CLASS_BATCH)

# machine-readable rejection reasons (the wire contract; tests and the
# metrics counters key on these strings)
REJECT_QUEUE_FULL = "queue_full"
REJECT_TOO_LONG = "too_long"
REJECT_BAD_REQUEST = "bad_request"
REJECT_DRAINING = "draining"        # queue closed for graceful shutdown
REJECT_SHED = "shed_deadline"       # brownout: deadline unmeetable now
REJECT_POISONED = "request_poisoned"  # crash-replay quarantine
REJECT_NO_REPLICA = "no_replica"    # router: no dispatchable replica
TIMED_OUT = "timed_out"


@dataclasses.dataclass
class Request:
    """One generation request plus its serving bookkeeping.

    `prompt_ids` is a dense int32 vector (no padding). Timestamps are
    host-monotonic; the metrics layer derives TTFT/TPOT/e2e from them.
    `sink` is set by the transport that owns the reply channel (None
    for in-process callers, which read `tokens` / wait on `done`)."""

    prompt_ids: np.ndarray
    max_new_tokens: int
    id: str = ""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    deadline_s: float | None = None      # SLO relative to submission
    sla_class: str = CLASS_INTERACTIVE   # interactive | batch
    tenant: str | None = None            # workload attribution label
    trace: dict | None = None            # fleet hop context (router-stamped)
    sink: Callable[[dict], Any] | None = None

    # --- runtime state (engine-owned) ---
    submitted_at: float = 0.0
    prefilled_at: float | None = None
    first_token_at: float | None = None
    finished_at: float | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    status: str = "queued"  # queued|active|done|rejected|timed_out

    # --- per-phase attribution (engine-owned; seconds) ---
    # Every instant of a request's life lands in exactly one bucket, so
    # the consumer (`obs trace`) can decompose TTFT/e2e without guessing:
    #   queue_wait  — FIFO wait before the first slot admission
    #   gate_wait   — the tail of a queue wait spent denied by the
    #                 block-availability gate (pool pressure, not FIFO)
    #   prefill     — the initial prefill call (suffix compute)
    #   decode      — in-slot tick time between emissions, net of ALL
    #                 transport-sink writes in the gap (the engine nets
    #                 at accumulation time: own writes are charged to
    #                 client_write, a neighbour's slow client must not
    #                 masquerade as this slot's decode)
    #   replay      — preemption cost: re-queue wait + re-prefill of
    #                 prompt+generated after a pool-exhaustion eviction
    #   client_write— time inside the transport sink (slow consumers)
    enqueued_at: float = 0.0           # (re)joined the queue at
    admitted_at: float | None = None   # last queue pop
    gate_blocked_at: float | None = None  # first block-gate denial at head
    queue_wait_s: float = 0.0
    gate_wait_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    replay_s: float = 0.0
    client_write_s: float = 0.0
    preempts: int = 0
    finish_reason: str | None = None   # eos|budget|rejected|timed_out
    _preempted: bool = False           # next pop is a replay resume
    # --- crash-safety bookkeeping (serve/journal.py) ---
    replays: int = 0                   # journal crash-replay count
    _journaled: bool = False           # has an admit record on the WAL
    clamped_from: int | None = None    # brownout clamp: original max_new

    def __post_init__(self):
        self.prompt_ids = np.asarray(self.prompt_ids, np.int32).reshape(-1)
        if self.sla_class not in SLA_CLASSES:
            self.sla_class = CLASS_INTERACTIVE
        if not self.id:
            self.id = f"req_{next(_ids)}"
        if not self.submitted_at:
            # construction-time stamp only; `submit` restamps at the
            # door with the queue's own (possibly virtual) clock
            self.submitted_at = SYSTEM()
        if not self.enqueued_at:
            self.enqueued_at = self.submitted_at

    @property
    def prompt_len(self) -> int:
        return int(self.prompt_ids.shape[0])

    @property
    def deadline_at(self) -> float | None:
        if self.deadline_s is None:
            return None
        return self.submitted_at + self.deadline_s

    def phases_s(self) -> dict[str, float]:
        """Per-phase totals keyed by the canonical phase vocabulary
        (`obs/timeline.py:PHASES`). THE field→phase mapping: every
        producer (the `request_finished` event, the phase histograms,
        loadgen's attribution) builds from this one dict, so a
        new phase is wired in here once or the reporters silently
        disagree."""
        return {
            "queue_wait": self.queue_wait_s,
            "gate_wait": self.gate_wait_s,
            "prefill": self.prefill_s,
            "decode": self.decode_s,
            "preempt_replay": self.replay_s,
            "client_write": self.client_write_s,
        }


class AdmissionQueue:
    """Bounded per-class FIFOs with reject-with-reason, weighted-fair
    pops, and a prefill-token budget per scheduling round.

    Two SLO classes (`SLA_CLASSES`) each own a FIFO deque. `pop_ready`
    serves them WEIGHTED-FAIR: a deterministic repeating pattern built
    from `class_weights` (default 3 interactive picks per batch pick)
    with a persistent cursor, skipping empty classes — so batch work
    always progresses (no starvation) but interactive requests never
    wait behind a deep batch backlog. Within a class, order is strict
    FIFO and a block-gated head stalls only its OWN class; the other
    class keeps flowing (`gate_blocked` names the stalled classes so
    the engine can preempt batch slots for a gated interactive head).
    """

    def __init__(
        self,
        capacity: int = 64,
        *,
        max_total_tokens: int,
        prefill_budget: int = 512,
        class_weights: dict[str, int] | None = None,
        class_capacity: dict[str, int] | None = None,
        class_deadline_s: dict[str, float] | None = None,
        clock: Callable[[], float] | None = None,
    ):
        """`max_total_tokens` = the engine's per-slot cache length: a
        request whose prompt + max_new_tokens cannot fit is rejected at
        the door (it could never complete). `prefill_budget` caps the
        prompt tokens admitted per `pop_ready` round. `class_capacity`
        caps one class's depth BELOW the shared capacity (a batch
        tenant must not fill the whole queue); `class_deadline_s`
        stamps a default deadline on submit when the request carries
        none — the hook that makes batch work sheddable under brownout
        even when clients never state an SLO."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.max_total_tokens = max_total_tokens
        self.prefill_budget = max(1, prefill_budget)
        weights = {CLASS_INTERACTIVE: 3, CLASS_BATCH: 1,
                   **(class_weights or {})}
        # the deterministic service pattern weighted-fair rounds walk:
        # e.g. weights {interactive:3, batch:1} -> I,I,I,B repeating
        self._pattern: tuple[str, ...] = tuple(
            cls for cls in SLA_CLASSES
            for _ in range(max(1, int(weights.get(cls, 1)))))
        self._wrr = 0   # persistent cursor into the pattern
        self.class_capacity = dict(class_capacity or {})
        self.class_deadline_s = dict(class_deadline_s or {})
        self._qs: dict[str, deque[Request]] = {
            cls: deque() for cls in SLA_CLASSES}
        # classes whose head was denied by the block gate in the LAST
        # pop_ready round (the engine's preempt-batch-for-interactive
        # trigger reads this)
        self.gate_blocked: frozenset[str] = frozenset()
        self._lock = threading.Lock()
        self._closed: str | None = None  # reject reason once closed
        # every time read in this queue goes through the injected clock
        # so the fleet simulator can run it on virtual time
        self._clock = clock if clock is not None else SYSTEM

    # ------------------------------------------------------------ admit

    def submit(self, req: Request) -> tuple[bool, str | None]:
        """(accepted, reject_reason). Rejection is immediate and final —
        the caller owns retry policy, the queue never buffers beyond
        `capacity`."""
        # a request may be constructed long before it is handed over
        # (loadgen builds its whole arrival schedule up front): the life
        # clock — TTFT/e2e/deadline/queue_wait — starts at the door,
        # else pre-submit idle time masquerades as queue wait
        req.submitted_at = req.enqueued_at = self._clock()
        if self._closed is not None:
            # graceful drain: the door is shut, in-flight work finishes.
            # Checked first — a draining server's answer is "go away",
            # not a validation report.
            req.status = "rejected"
            return False, self._closed
        if req.max_new_tokens < 1:
            req.status = "rejected"
            return False, REJECT_BAD_REQUEST
        if req.prompt_len < 1:
            req.status = "rejected"
            return False, REJECT_BAD_REQUEST
        if req.prompt_len + req.max_new_tokens > self.max_total_tokens:
            req.status = "rejected"
            return False, REJECT_TOO_LONG
        cls = req.sla_class
        if req.deadline_s is None and self.class_deadline_s.get(cls):
            # default class deadline, relative to the door stamp above —
            # the deadline_at property reads submitted_at, already set
            req.deadline_s = float(self.class_deadline_s[cls])
        with self._lock:
            depth = sum(len(q) for q in self._qs.values())
            cap = self.class_capacity.get(cls)
            if depth >= self.capacity or \
                    (cap is not None and len(self._qs[cls]) >= cap):
                req.status = "rejected"
                return False, REJECT_QUEUE_FULL
            self._qs[cls].append(req)
        return True, None

    # ------------------------------------------------------------- pops

    def pop_ready(
        self, n_slots: int, now: float | None = None,
        can_admit: Callable[[Request], bool] | None = None,
    ) -> tuple[list[Request], list[Request]]:
        """(admit, timed_out) for one scheduling round.

        FIFO order, at most `n_slots` requests, at most
        `prefill_budget` total prompt tokens — except that a head
        request whose prompt alone exceeds the budget is admitted when
        nothing else has been this round (otherwise it would starve
        forever). Expired requests are dropped here, at the last moment
        before their prefill would be paid.

        `can_admit` is the engine's block-availability gate (paged KV
        cache): a head whose worst-case block demand does not fit stays
        queued — and blocks everything behind it IN ITS CLASS,
        deliberately, because skipping ahead would starve large
        requests exactly the way the prefill budget refuses to. The
        OTHER class keeps flowing, and `self.gate_blocked` names the
        stalled classes after the round so the engine can react (a
        gated interactive head is the preempt-batch trigger). The gate
        is consulted last, immediately before the pop, so a True
        return (which reserves blocks) always corresponds to a popped
        request."""
        now = self._clock() if now is None else now
        admit: list[Request] = []
        expired: list[Request] = []
        budget = self.prefill_budget
        gated: set[str] = set()
        stalled: set[str] = set()   # gate- or budget-stalled this round
        n_pat = len(self._pattern)
        with self._lock:
            while len(admit) < n_slots:
                chosen: str | None = None
                step = 0
                for off in range(n_pat):
                    cls = self._pattern[(self._wrr + off) % n_pat]
                    if cls in stalled:
                        continue
                    q = self._qs[cls]
                    while q:   # expire this class's head(s) first
                        head = q[0]
                        dl = head.deadline_at
                        if dl is not None and now > dl:
                            q.popleft()
                            head.status = TIMED_OUT
                            expired.append(head)
                            continue
                        break
                    if not q:
                        continue
                    head = q[0]
                    if head.prompt_len > budget and admit:
                        # this class waits for next round's fresh
                        # budget; the other class may still fit
                        stalled.add(cls)
                        continue
                    if can_admit is not None and not can_admit(head):
                        # pool pressure: this class waits for blocks.
                        # Stamp the FIRST denial so the engine can
                        # split this head's wait into FIFO time vs
                        # block-gate time.
                        if head.gate_blocked_at is None:
                            head.gate_blocked_at = now
                        stalled.add(cls)
                        gated.add(cls)
                        continue
                    chosen = cls
                    step = off
                    break
                if chosen is None:
                    break
                head = self._qs[chosen].popleft()
                head.status = "active"
                head.admitted_at = now
                admit.append(head)
                budget -= head.prompt_len
                # the cursor advances past the pattern slot just
                # served, so class service stays weighted across
                # rounds, not just within one
                self._wrr = (self._wrr + step + 1) % n_pat
                if budget <= 0:
                    break
            self.gate_blocked = frozenset(gated)
        return admit, expired

    def push_front(self, req: Request) -> None:
        """Re-queue at the HEAD, bypassing capacity: used for preempted
        (or allocation-raced) requests that were already admitted once —
        they resume first, so preemption degrades latency, never
        fairness."""
        req.status = "queued"
        req.enqueued_at = self._clock()
        with self._lock:
            self._qs[req.sla_class].appendleft(req)

    def close(self, reason: str = REJECT_DRAINING) -> None:
        """Shut the door: every later `submit` rejects with `reason`.
        Requests already queued are unaffected — drain means finishing
        what was accepted, not abandoning it."""
        with self._lock:
            self._closed = reason

    @property
    def closed(self) -> bool:
        return self._closed is not None

    def shed_doomed(self, now: float | None = None,
                    est_wait_s: float = 0.0, *,
                    est_wait_by_class: dict[str, float] | None = None,
                    classes: tuple[str, ...] | None = None,
                    ) -> list[Request]:
        """Brownout shedding, deadline-aware AND class-aware: remove
        queued requests whose deadline cannot be met even if service
        began after their CLASS's estimated wait. These are the
        CHEAPEST requests to shed — they are already doomed, so
        rejecting them now costs the client a fast retry signal instead
        of a slow guaranteed timeout, and frees queue positions for
        requests that can still win.

        The estimate is per class (`est_wait_by_class`, falling back to
        the scalar `est_wait_s`): the classes drain independently under
        weighted-fair service, so a deep batch backlog's wait must
        never doom-shed an interactive request that would actually be
        scheduled next. `classes` restricts the sweep (the engine sheds
        batch first and touches interactive only when batch is empty).
        Returned soonest-deadline first (most-doomed first); requests
        without deadlines are never shed here — with no SLO stated, the
        queue cannot call them hopeless."""
        now = self._clock() if now is None else now
        shed: list[Request] = []
        by_cls = est_wait_by_class or {}
        with self._lock:
            for cls in (classes if classes is not None else SLA_CLASSES):
                est = float(by_cls.get(cls, est_wait_s))
                alive: deque[Request] = deque()
                for r in self._qs[cls]:
                    dl = r.deadline_at
                    if dl is not None and dl < now + est:
                        r.status = "rejected"
                        shed.append(r)
                    else:
                        alive.append(r)
                self._qs[cls] = alive
        shed.sort(key=lambda r: r.deadline_at)
        return shed

    def drop_expired(self, now: float | None = None) -> list[Request]:
        """Sweep expired requests without admitting (used while all
        slots are busy so waiting requests still time out on time)."""
        now = self._clock() if now is None else now
        expired: list[Request] = []
        with self._lock:
            for cls in SLA_CLASSES:
                alive: deque[Request] = deque()
                for r in self._qs[cls]:
                    dl = r.deadline_at
                    if dl is not None and now > dl:
                        r.status = TIMED_OUT
                        expired.append(r)
                    else:
                        alive.append(r)
                self._qs[cls] = alive
        return expired

    def __len__(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._qs.values())

    @property
    def depth(self) -> int:
        return len(self)

    def depth_of(self, sla_class: str) -> int:
        with self._lock:
            return len(self._qs.get(sla_class, ()))

    def depth_by_class(self) -> dict[str, int]:
        """Per-class depths in one lock acquisition (the exposition
        payload and `obs top`'s per-class columns read this)."""
        with self._lock:
            return {cls: len(q) for cls, q in self._qs.items()}


class BrownoutGovernor:
    """Hysteretic overload detector — the state machine behind
    `--brownout`.

    Overload has two observable signatures at the queue: depth growing
    (arrivals outpace drains) and queue-wait p95 growing (the user-felt
    version of the same fact, which also catches a slow engine at
    constant depth). The governor watches both and flips `active` with
    **hysteresis** — enter at the high watermarks, exit only when BOTH
    signals are back under the low ones — so a load hovering at the
    threshold browns out once, not every other tick (flapping would
    turn the clamp into output-length jitter and the shed into a
    lottery).

    Host-only and engine-agnostic on purpose: `update()` takes numbers
    and returns a transition, so the hysteresis contract is unit-
    testable without a model, a device, or a clock."""

    def __init__(self, *, depth_high: int, depth_low: int | None = None,
                 wait_high_s: float = 0.0, wait_low_s: float | None = None,
                 window: int = 64):
        if depth_high < 1 and wait_high_s <= 0:
            raise ValueError("brownout needs a depth or wait watermark")
        self.depth_high = depth_high
        self.depth_low = depth_low if depth_low is not None \
            else max(0, depth_high // 2)
        self.wait_high_s = wait_high_s
        self.wait_low_s = wait_low_s if wait_low_s is not None \
            else wait_high_s / 2.0
        self._waits: deque[float] = deque(maxlen=max(4, window))
        # per-class windows ride along so shed_doomed can use a CLASS's
        # own wait estimate (a batch backlog's p95 must not doom
        # interactive heads); the merged window stays the hysteresis
        # signal — overload is a whole-queue condition
        self._class_waits: dict[str, deque[float]] = {
            cls: deque(maxlen=max(4, window)) for cls in SLA_CLASSES}
        self.active = False

    def observe_wait(self, wait_s: float, sla_class: str | None = None,
                     ) -> None:
        """Feed one completed queue wait (the engine calls this at each
        pop — the only moment a wait's true length is known)."""
        self._waits.append(float(wait_s))
        if sla_class in self._class_waits:
            self._class_waits[sla_class].append(float(wait_s))

    def wait_p95(self, sla_class: str | None = None) -> float:
        win = self._waits if sla_class is None \
            else self._class_waits.get(sla_class)
        if not win:
            return 0.0
        from hyperion_tpu.obs.registry import percentile

        return float(percentile(list(win), 95))

    def update(self, depth: int) -> str | None:
        """Advance the state machine; returns "enter"/"exit" on a
        transition, None otherwise."""
        p95 = self.wait_p95()
        if not self.active:
            over = (self.depth_high > 0 and depth >= self.depth_high) or \
                (self.wait_high_s > 0 and p95 >= self.wait_high_s)
            if over:
                self.active = True
                return "enter"
            return None
        under = (self.depth_high <= 0 or depth <= self.depth_low) and \
            (self.wait_high_s <= 0 or p95 <= self.wait_low_s)
        if under:
            self.active = False
            # the waits that tripped the watermark are history the
            # moment we recover — keeping them would re-trip the next
            # update from stale evidence
            self._waits.clear()
            for win in self._class_waits.values():
                win.clear()
            return "exit"
        return None
