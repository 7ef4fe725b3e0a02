"""Slot-based continuous-batching decode engine — Orca-style iteration
scheduling on a paged, prefix-shared TPU cache.

The single-shot path (`infer/generate.py`) decodes ONE batch of aligned
prompts: prefill, then a `lax.scan` that every request enters and
leaves together. A server cannot batch that way — requests arrive when
they arrive, finish when they finish, and a batch that waits for its
slowest member wastes every other slot's ticks. Continuous batching
(Yu et al., OSDI '22) decouples the two: the unit of scheduling is one
decode TICK, and membership of the batch is re-decided between ticks.

TPU constraint that shapes everything here: **recompilation is the
enemy.** XLA specializes on shapes, so every device-side structure is
shape-fixed at construction and the tick/prefill executables compile
once, at warmup, forever:

  * The KV cache is a `[num_blocks, block_size]` POOL
    (`models/llama.py:init_paged_cache`), not a per-slot slab. A slot
    addresses it through a block table (`serve/blocks.py`): logical
    position p lives at physical block `bt[slot, p // bs]`. HBM burn
    tracks tokens actually held, not `slots × max_len`, and two slots
    whose prompts share a prefix share the physical blocks outright
    (PagedAttention — Kwon et al., SOSP '23). The table itself is a
    tiny `[S, MB]` int32 host array shipped with each jitted call, so
    block churn never touches compiled code. The pool is built BY
    LAYER KIND (`model.cfg.layer_kinds`): one pool size, one block
    manager and one block table per kind. A `full` layer keeps every
    position of a request; a `window` layer keeps the positions its
    queries can still see, its leading blocks going back to the free
    list as the request moves on (`_slide_windows`).
  * Every per-request quantity the tick needs — cache depth, eos
    latch, remaining budget, temperature/top_k/top_p, PRNG key — is a
    `[S]` device array threaded through the jitted call, so slot
    churn is a cheap scatter into state rows, never a retrace.
  * A radix prefix cache (`serve/blocks.py:RadixPrefixCache`) maps
    token prefixes to retained block chains: a shared system prompt is
    prefilled ONCE, and every later request that starts with it skips
    straight to its own suffix — the prefill jit runs on the suffix
    bucket, attending over the shared blocks through the table. A
    prompt that diverges mid-block still reuses the agreeing positions
    via one copy-on-write block copy (the `copy` jit).
  * Admission is block-aware: the queue only pops a request when its
    worst-case block demand fits (`can_admit` — free + evictable
    radix blocks minus outstanding reservations). Under `optimistic`
    admission the pool can still exhaust mid-decode; the engine then
    PREEMPTS the youngest slot back to the queue head (its generated
    tokens ride along and re-prefill, usually from its own still-
    cached prefix) instead of crashing.

  * Speculative decoding (`spec_k` + `serve/draft.py`) turns the tick
    into a draft/verify/accept round: a host-side draft source
    proposes up to k tokens per slot, ONE batched target forward over
    a `[S, k+1]` window scores all slots' proposals through the same
    paged path (vector `cache_index` + per-row position masks), and a
    fully static accept-masked select emits the longest prefix the
    target agrees with plus its own correction — 1..k+1 tokens per
    slot per tick, one executable per (S, k), zero retraces.

Semantics contract (the oracle `tests/test_serve.py` pins): at
temperature 0 a request decoded through this engine — while other
slots churn, share its blocks, or preempt around it, with or without
speculation — emits **bit-identical tokens** to
`infer/generate.generate` on the same prompt. K/V at position p depend
only on tokens 0..p, so shared blocks hold exactly the values each
sharer would have computed, and every per-slot op is row-independent;
the acceptance rule only ever keeps tokens the target itself would
have produced.
"""

from __future__ import annotations

import dataclasses
import itertools
import sys
import time

from hyperion_tpu.utils.clock import SYSTEM as _CLOCK
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from hyperion_tpu.infer.generate import sample_token_slots
from hyperion_tpu.infer.speculative import accept_draft
from hyperion_tpu.serve.draft import DraftSource, NgramDraft
from hyperion_tpu.serve.blocks import (
    BlockManager,
    RadixPrefixCache,
    SeqAlloc,
    blocks_for,
)
from hyperion_tpu.serve.hostcache import (
    HostBlockStore,
    HotRootTracker,
    prefix_root_digest,
)
from hyperion_tpu.obs import slo as slo_mod
from hyperion_tpu.obs.export import DEFAULT_WINDOW_S
from hyperion_tpu.obs.heartbeat import host_rss_mb as hb_host_rss_mb
from hyperion_tpu.obs.ledger import CompileLedger
from hyperion_tpu.obs.tickprof import (
    EXPERT_ROW_COUNTERS,
    PROMPT_READ_COUNTERS,
    WALK_COUNTERS,
    WRITE_COUNTERS,
    FlightRecorder,
    TickProfiler,
    null_flight_recorder,
)
from hyperion_tpu.serve.journal import MAX_REPLAYS_DEFAULT
from hyperion_tpu.serve.metrics import ServeMetrics
from hyperion_tpu.utils.profiling import annotate
from hyperion_tpu.serve.queue import (
    CLASS_BATCH,
    CLASS_INTERACTIVE,
    REJECT_BAD_REQUEST,
    REJECT_DRAINING,
    REJECT_POISONED,
    REJECT_SHED,
    AdmissionQueue,
    BrownoutGovernor,
    Request,
)

_SNAPSHOT_EVERY = 32  # ticks between metric snapshots on the stream

# `_admit`'s third outcome: the slot was claimed but prefill proceeds
# in chunks across later steps (distinct from None = allocation race,
# which requeues). No token exists yet; the caller just moves on.
_CHUNK_ADMIT = object()


# --- the three compiled surfaces, shared process-wide -----------------
# Module-level bodies with the model/eos/pad as STATIC jit arguments:
# every Engine in a process shares one jit cache per surface, so two
# engines over the same model and shapes (the test suite's shape, and
# any multi-engine deployment's) compile each executable exactly once.
# What runs here outside the model has no module boundary to name it in
# a device trace, so it sits under `jax.named_scope`s: `sampling`,
# `slot_state`, `kv_copy` (obs/xprof.py groups device time by them).

def _tick_impl(model, eos_id, pad_id, variables, cache, st, bt, live):
    # every live slot advances one token: write last_token's K/V at
    # its own depth through its block-table row, attend its own
    # filled prefix (gathered from the pool), sample with its own
    # params. Dead lanes (freed or preempted — `live` is the host's
    # slot table shipped as a mask) still compute but write to the
    # null block and emit pad.
    act = st["active"] & live
    # `tick_stats`: what a model sows for the tick record (an expert
    # model its rows' picks, models/afmoe.py); nothing for most models
    (logits, cache), sown = model.apply(
        variables, st["last_token"][:, None],
        cache=cache, cache_index=st["lengths"], block_tables=bt,
        mutable=["tick_stats"],
    )
    with jax.named_scope("sampling"):
        keys = jax.vmap(jax.random.fold_in)(st["keys"], st["lengths"])
        nxt = sample_token_slots(
            logits[:, 0], keys,
            st["temperature"], st["top_k"], st["top_p"], live=act,
        )
    with jax.named_scope("slot_state"):
        nxt = jnp.where(act, nxt, jnp.int32(pad_id))
        adv = act.astype(jnp.int32)
        gen = st["generated"] + adv
        lengths = st["lengths"] + adv
        hit_eos = (nxt == eos_id) if eos_id is not None \
            else jnp.zeros_like(act)
        finished = act & (hit_eos | (gen >= st["budget"]))
        st = {
            **st,
            "last_token": jnp.where(act, nxt, st["last_token"]),
            "generated": gen,
            "lengths": lengths,
            "active": act & ~finished,
        }
        counted = _expert_counters(sown, act)
    return cache, st, nxt, finished, counted


def _expert_counters(sown, act) -> dict:
    """The tick record's expert counters from what the expert layers
    sowed (`expert_load` [S, held] per layer: which held experts each
    row picked), over the rows the tick advanced: picks that landed on
    held experts, held experts with at least one token (both summed
    over layers), and the busiest expert's tokens. `{}` for a model
    that sows nothing: its program is what it was."""
    loads = jax.tree.leaves(sown)
    if not loads:
        return {}
    per_expert = jnp.sum(
        jnp.stack(loads) * act[None, :, None].astype(jnp.int32), axis=1)
    return {"expert_picks_held": jnp.sum(per_expert),
            "experts_touched": jnp.sum(per_expert > 0),
            "expert_load_max": jnp.max(per_expert)}


def _spec_tick_impl(model, eos_id, pad_id, variables, cache, st, bt, live,
                    drafts):
    # the speculative tick: every live slot advances 1..k+1 tokens in
    # ONE target forward. The verify window [last_token, d_1..d_k]
    # writes K/V at positions lengths..lengths+k through each slot's
    # block-table row (the paged path takes a [S]-vector cache_index
    # and spans T positions per row — models/llama.py), and row i's
    # logits predict position lengths+i+1. Acceptance per slot is the
    # shared longest-agreeing-prefix rule (infer/speculative.py), so
    # temp-0 output is bit-identical to sequential decode; rejected
    # positions hold stale K/V that the causal mask keeps invisible
    # until the next window idempotently overwrites them. Every update
    # below is an accept-MASKED select over static [S, k+1] shapes —
    # never a dynamic slice — so one executable serves every
    # acceptance pattern and `compile_stats()` stays flat.
    act = st["active"] & live
    k = drafts.shape[1]
    window = jnp.concatenate([st["last_token"][:, None], drafts], axis=1)
    logits, cache = model.apply(
        variables, window,
        cache=cache, cache_index=st["lengths"], block_tables=bt,
    )
    # t[s, i] = the token the SEQUENTIAL tick would emit at position
    # lengths[s]+i given this window prefix: greedy rows take argmax;
    # temp>0 rows draw with the slot key folded at that position —
    # the exact fold the sequential tick performs — so a seeded
    # sampling stream is unchanged whether its drafts hit or miss
    with jax.named_scope("sampling"):
        pos = st["lengths"][:, None] \
            + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        keys = jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
            st["keys"], pos)
        t_arr = jax.vmap(
            lambda lg, ky: sample_token_slots(
                lg, ky, st["temperature"], st["top_k"], st["top_p"],
                live=act),
            in_axes=1, out_axes=1,
        )(logits, keys)  # [S, k+1]
        m, v = accept_draft(drafts, t_arr)
    with jax.named_scope("slot_state"):
        # emit v[:, j] iff j is within the accepted prefix (+correction),
        # within the remaining budget, and no earlier eos in the window —
        # active rows always emit >= 1 (j=0 is the correction of an empty
        # prefix and budget >= 1 while active), matching the sequential
        # tick's liveness
        iota = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        is_eos = (v == eos_id) if eos_id is not None \
            else jnp.zeros(v.shape, bool)
        eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
            - is_eos.astype(jnp.int32)
        remaining = st["budget"] - st["generated"]
        emit = (iota <= m[:, None]) & (iota < remaining[:, None]) \
            & (eos_before == 0) & act[:, None]
        cnt = emit.sum(axis=1).astype(jnp.int32)
        out = jnp.where(emit, v, jnp.int32(pad_id))
        last_i = jnp.maximum(cnt - 1, 0)[:, None]
        last = jnp.take_along_axis(v, last_i, axis=1)[:, 0]
        ended_eos = jnp.take_along_axis(is_eos, last_i, axis=1)[:, 0] \
            & (cnt > 0)
        gen = st["generated"] + cnt
        finished = act & (ended_eos | (gen >= st["budget"]))
        st = {
            **st,
            "last_token": jnp.where(act & (cnt > 0), last,
                                    st["last_token"]),
            "generated": gen,
            "lengths": st["lengths"] + cnt,
            "active": act & ~finished,
        }
        # accepted DRAFTS only (the correction token is a normal decode
        # token, not a draft win) — what the acceptance-rate gauge reads
        acc = jnp.minimum(m, cnt)
    return cache, st, out, cnt, acc, finished


def _prefill_impl(model, eos_id, variables, cache, st, prompt, bt_row,
                  slot, start, true_len, temperature, top_k, top_p,
                  budget, key):
    # prompt [1, Pb]: the UNCACHED suffix, bucket-padded, whose
    # positions are start..start+Pb-1. `start` > 0 is a prefix-cache
    # hit: positions 0..start-1 already sit in shared blocks of bt_row
    # and are attended, never recomputed. Pad positions beyond the
    # table's coverage write to the null block (the model routes
    # them); pad K/V inside the tail block is masked until decode
    # overwrites it position by position. Compiled once per bucket.
    logits, cache = model.apply(
        variables, prompt, cache=cache, cache_index=start,
        block_tables=jax.tree.map(lambda row: row[None], bt_row),
    )
    with jax.named_scope("sampling"):
        last = jax.lax.dynamic_slice_in_dim(
            logits[0], true_len - 1, 1, axis=0)  # [1, V]
        # fold position = (total prompt length - 1): identical whether
        # the prefix came from cache or compute, so a hit never shifts
        # the sampling stream
        fkey = jax.random.fold_in(key, start + true_len - 1)
        first = sample_token_slots(
            last, fkey[None], temperature[None], top_k[None], top_p[None],
        )[0]
    with jax.named_scope("slot_state"):
        hit_eos = (first == eos_id) if eos_id is not None else False
        finished = jnp.logical_or(hit_eos, budget <= 1)
        st = {
            "lengths": st["lengths"].at[slot].set(start + true_len),
            "active": st["active"].at[slot].set(~finished),
            "last_token": st["last_token"].at[slot].set(first),
            "generated": st["generated"].at[slot].set(1),
            "budget": st["budget"].at[slot].set(budget),
            "temperature": st["temperature"].at[slot].set(temperature),
            "top_k": st["top_k"].at[slot].set(top_k),
            "top_p": st["top_p"].at[slot].set(top_p),
            "keys": st["keys"].at[slot].set(key),
        }
    return cache, st, first, finished


def _chunk_impl(model, variables, cache, window, bt_row, start):
    # one chunked-prefill segment (Sarathi-Serve, OSDI '24): write the
    # K/V of `window`'s positions start..start+C-1 through this slot's
    # block-table row and DISCARD the logits — no sampling happens
    # until the final segment runs through `_prefill_impl`, whose fold
    # position (total prompt length - 1) is independent of how the
    # prefix was produced, so chunking never shifts the sampling
    # stream. K/V at position p depend only on tokens 0..p, which every
    # earlier segment already wrote: the values are bit-identical to a
    # one-shot prefill of the same prompt. The window is a FIXED [1, C]
    # shape — one executable per chunk size, forever.
    _, cache = model.apply(
        variables, window, cache=cache, cache_index=start,
        block_tables=jax.tree.map(lambda row: row[None], bt_row),
    )
    return cache


def _copy_impl(cache, src, dst):
    # whole-block K/V copy (copy-on-write fork): dst becomes a private
    # duplicate the writer may overwrite from its divergence offset
    # onward. src/dst are [C] vectors so one executable serves every
    # fork.
    with jax.named_scope("kv_copy"):
        return [
            {kv: layer[kv].at[dst].set(layer[kv][src])
             for kv in ("k", "v")}
            for layer in cache
        ]


_SHARED_JITS: dict[bool, tuple] = {}


def _shared_jits(donate: bool) -> tuple:
    """(tick, prefill, copy, spec_tick, chunk) jit objects, one set per
    donation mode. Donation keeps the pool + state slabs in place on
    real chips; the CPU backend ignores donation with a warning, so
    callers pass donate=False there. The spec tick specializes on the
    drafts array's [S, k] shape, so one executable serves a given
    (slots, k) forever — k is a config constant, never a retrace; the
    chunk jit likewise specializes on the [1, C] window, one executable
    per chunk size."""
    if donate not in _SHARED_JITS:
        _SHARED_JITS[donate] = (
            jax.jit(_tick_impl, static_argnums=(0, 1, 2),
                    donate_argnums=(4, 5) if donate else ()),
            jax.jit(_prefill_impl, static_argnums=(0, 1),
                    donate_argnums=(3, 4) if donate else ()),
            jax.jit(_copy_impl,
                    donate_argnums=(0,) if donate else ()),
            jax.jit(_spec_tick_impl, static_argnums=(0, 1, 2),
                    donate_argnums=(4, 5) if donate else ()),
            jax.jit(_chunk_impl, static_argnums=(0,),
                    donate_argnums=(2,) if donate else ()),
        )
    return _SHARED_JITS[donate]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    slots: int = 4                 # S: concurrent requests in flight
    max_len: int = 0               # L: per-slot logical length (0 = model max)
    eos_id: int | None = None
    pad_id: int = 0
    queue_capacity: int = 64
    prefill_budget: int = 512      # prompt tokens admitted per round
    min_bucket: int = 8            # smallest prefill padding bucket
    snapshot_every: int = _SNAPSHOT_EVERY
    # ---- paged cache ----
    block_size: int = 16           # tokens per KV block
    num_blocks: int = 0            # pool size incl. null block (0 = auto:
    #                                slots * ceil(L/bs) + 1, the slab
    #                                equivalent); of the `full` layer kind: a
    #                                windowed kind's pool is always its worst
    #                                case, slots * (window + a prefill piece)
    prefix_cache: bool = True      # radix prefix reuse on/off
    # ---- tiered KV (serve/hostcache.py) ----
    # > 0 enables the host-RAM spill tier: radix eviction demotes cold
    # prefix chains to host numpy buffers under this LRU budget, and a
    # later same-prefix admission restores them with one H2D scatter
    # per block instead of a re-prefill. Needs the prefix cache on.
    host_cache_mb: int = 0
    # where the store serializes on drain (empty = no persistence):
    # a spilled chain outlives the process, riding the journal's
    # recovery path — restart between evict and rehit still restores
    host_cache_dir: str = ""
    # "reserve": a request only admits when its WORST-CASE block demand
    # (prompt + full budget, minus shared prefix) is covered — pool
    # exhaustion is impossible by accounting. "optimistic": admit on
    # prompt-fit only, oversubscribe the growth, and preempt-to-queue
    # when the pool runs dry (vLLM's default posture; higher occupancy,
    # tail-latency risk under pathological growth).
    admission: str = "reserve"
    # ---- speculative decoding (serve/draft.py) ----
    # spec_k > 0 with a draft source turns each decode tick into a
    # draft/verify/accept round emitting 1..spec_k+1 tokens per slot;
    # temp-0 output stays bit-identical to sequential decode (the
    # accept rule only keeps tokens the target would have produced)
    spec_k: int = 0                # draft tokens per slot per tick (0 = off)
    draft: str = "off"             # "ngram" (self-drafting) | "off"
    # ---- SLO classes + chunked prefill (workload isolation) ----
    # prompts whose uncached suffix exceeds `prefill_chunk` prefill in
    # fixed [1, chunk] segments interleaved with decode ticks (one
    # segment per step) — co-running slots' TTFT stops spiking on
    # long-prompt admission, at one extra executable total
    prefill_chunk: int = 0         # 0 = one-shot prefill (off)
    interactive_weight: int = 3    # weighted-fair picks per pattern round
    batch_weight: int = 1
    batch_capacity: int = 0        # batch queue depth cap (0 = shared cap)
    batch_deadline_s: float = 0.0  # default batch deadline (0 = none) —
    #                                what makes batch sheddable under
    #                                brownout when clients state no SLO
    # ---- overload brownout (serve/queue.py:BrownoutGovernor) ----
    brownout: bool = False         # enable the governor
    brownout_depth: int = 0        # enter watermark (0 = 3/4 of capacity)
    brownout_wait_s: float = 0.0   # queue-wait p95 enter watermark (0 = off)
    brownout_clamp: int = 0        # clamp max_new_tokens while active (0 = off)
    # ---- SLO burn-rate alerting (obs/slo.py) — 0 = that target off ----
    slo_ttft_p99_ms: float = 0.0   # windowed TTFT p99 must stay under this
    slo_reject_rate: float = 0.0   # windowed reject fraction budget
    slo_availability: float = 0.0  # windowed completed/(completed+failed) floor
    slo_fast_s: float = 0.0        # fast burn window (0 = obs/slo default 60s)
    slo_slow_s: float = 0.0        # slow burn window (0 = obs/slo default 600s)


@dataclasses.dataclass
class TokenEvent:
    """One emission the host routes to a transport/test."""
    request: Request
    token: int | None              # None for reject/timeout events
    finished: bool
    kind: str = "token"            # token | rejected | timed_out
    reason: str | None = None


def _tr(req) -> dict:
    """The request's fleet hop context as event attrs. Every
    request-scoped event splats this so a router-dispatched request's
    replica-side lifecycle joins the fleet trace by id; {} for direct
    clients, so local-only runs pay zero extra bytes."""
    trace = getattr(req, "trace", None)
    return {"trace": trace} if trace else {}


class Engine:
    """Continuous-batching engine over one model + one variables tree.

    Host-side it owns the slot table (slot index -> Request), block
    manager + radix cache, the admission queue, metrics, and telemetry;
    device-side the `[num_blocks, block_size]` KV pool and the [S]
    state rows. `step()` is one scheduling round (admit -> ensure
    blocks -> tick -> route); `run()` loops it."""

    def __init__(
        self,
        model: Any,
        variables: dict,
        cfg: EngineConfig,
        *,
        metrics: ServeMetrics | None = None,
        tracer=None,
        heartbeat=None,
        chaos=None,
        journal=None,
        on_event: Callable[[TokenEvent], Any] | None = None,
        flight_path=None,
    ):
        from hyperion_tpu.models.llama import (
            init_paged_cache,
            paged_cache_block_bytes,
            select_paged_attn_impl,
        )
        from hyperion_tpu.ops.attention import window_view_blocks
        from hyperion_tpu.obs import heartbeat as hb_mod
        from hyperion_tpu.obs import trace as trace_mod

        self.model = model
        # once, here: a host-side tree (the server hands over the
        # export as numpy) would otherwise be uploaded whole by every
        # jit call; arrays already on a device stay where they are
        self.variables = jax.device_put(variables)
        mcfg = model.cfg
        L = cfg.max_len or mcfg.max_len
        if L > mcfg.max_len:
            raise ValueError(
                f"engine max_len {L} exceeds model max_len {mcfg.max_len}")
        if cfg.admission not in ("reserve", "optimistic"):
            raise ValueError(f"admission must be 'reserve' or 'optimistic', "
                             f"got {cfg.admission!r}")
        if cfg.draft not in ("off", "ngram"):
            raise ValueError(
                f"draft must be 'off' or 'ngram', got {cfg.draft!r}")
        if cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {cfg.spec_k}")
        # speculation needs both a window (spec_k) and a proposer
        # (draft): either alone leaves the sequential tick in charge
        self._spec = cfg.spec_k > 0 and cfg.draft != "off"
        self._drafter: DraftSource | None = \
            NgramDraft() if self._spec else None
        bs = cfg.block_size
        self._mb = blocks_for(L, bs)          # block-table width per slot
        # a looped model (models/ouro.py) applies its layers
        # `cache_steps` times a token and keeps a cache a step and
        # layer, `cache_segments` of them in each pool array, all behind
        # the one table; every other model 1 and 1. A block id then
        # names `bs` positions in every segment: block bytes, the
        # copy-on-write copy and the host tier's payload follow
        self._steps: int = getattr(mcfg, "cache_steps", 1)
        self._segments: int = getattr(mcfg, "cache_segments", 1)
        # what a decode tick of such a model adds to its record: the
        # steps it ran a row and the cache layers it wrote and read,
        # from the config (no device read); nothing for the others
        self._loop_per_tick = {
            "loop_steps": self._steps,
            "layer_passes": self._steps * mcfg.n_layers,
        } if self._steps > 1 else {}
        # the cache by layer kind: {kind: window}, 0 = every position.
        # `self.mgr`, `self._bt`, `self._seqs` are the first kind's
        # (`full` where the model has one): the chain that carries a
        # slot's order and write frontier
        self._kinds: dict[str, int] = dict(sorted(
            mcfg.layer_kinds, key=lambda kw: kw[0] != "full"))
        windowed = {k: w for k, w in self._kinds.items() if w}
        self._windowed = bool(windowed)
        if windowed:
            # a windowed layer lets blocks go that these features count
            # on finding again: refuse loudly, nothing silently off
            for on, what, why in (
                (cfg.prefix_cache, "the radix prefix cache (prefix_cache)",
                 "a later request would share a chain whose blocks the "
                 "window has already freed"),
                (cfg.host_cache_mb > 0, "the host spill tier "
                 "(host_cache_mb)", "it restores prefix chains, and needs "
                 "the prefix cache"),
                (self._spec, "speculative decoding (spec_k, draft)",
                 "no test holds its verify window to a sliding chain"),
            ):
                if on:
                    raise ValueError(
                        f"{type(model).__name__} has windowed layers "
                        f"{windowed}: the engine cannot combine them with "
                        f"{what} yet, because {why}. Turn it off.")
        # most blocks of a kind one slot holds at a time: the whole
        # chain, or a window plus the longest piece one call prefills
        piece = cfg.prefill_chunk or L
        self._hold = {
            k: min(self._mb, blocks_for(w + piece, bs) + 2) if w
            else self._mb for k, w in self._kinds.items()}
        # blocks of its table a decode tick reads for one slot
        self._view = {
            k: min(self._mb, window_view_blocks(w, 1, bs)) if w
            else self._mb for k, w in self._kinds.items()}
        # how the decode tick reads the pools, "pallas" (in place, the
        # paged-attention kernel) or "gather": the model's choice,
        # resolved as the model resolves it for the tick's window; a
        # model without an option of its own asks the selector, as its
        # attention does per call.
        self._tick_width = cfg.spec_k + 1 if self._spec else 1
        self._read_for = mcfg.paged_attn_for \
            if hasattr(mcfg, "paged_attn_for") else lambda width: \
            select_paged_attn_impl(
                width, mcfg.n_heads // mcfg.n_kv_heads,
                jax.default_backend())
        self._tick_read = self._read_for(self._tick_width)
        num_blocks = cfg.num_blocks or cfg.slots * self._mb + 1
        if num_blocks < self._mb + 1:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold one worst-case "
                f"request ({self._mb} blocks + the null block); raise "
                f"--num-blocks or --block-size")
        if cfg.prefill_chunk < 0 or cfg.prefill_chunk > L:
            raise ValueError(
                f"prefill_chunk must be in [0, max_len={L}], "
                f"got {cfg.prefill_chunk}")
        self.cfg = dataclasses.replace(cfg, max_len=L, num_blocks=num_blocks)
        self.queue = AdmissionQueue(
            cfg.queue_capacity, max_total_tokens=L,
            prefill_budget=cfg.prefill_budget,
            class_weights={CLASS_INTERACTIVE: cfg.interactive_weight,
                           CLASS_BATCH: cfg.batch_weight},
            class_capacity={CLASS_BATCH: cfg.batch_capacity}
            if cfg.batch_capacity else None,
            class_deadline_s={CLASS_BATCH: cfg.batch_deadline_s}
            if cfg.batch_deadline_s else None,
        )
        # router-ordered batch-class brownout (the `class_brownout`
        # control verb): batch sheds/clamps as under the local governor,
        # but interactive is NEVER touched — the order says "this
        # replica is someone's overflow valve", not "this replica is
        # drowning". Written by the exporter thread, read by the engine
        # thread; a bool flip is atomic under the GIL.
        self._class_brownout = False
        # chunked-prefill slots: slot -> {req, prompt, budget, pos,
        # row, resumed}. While a slot chunks, its real block-table row
        # is held HERE and the device row stays zeroed: the decode tick
        # writes K/V at lengths[slot] for every lane regardless of the
        # live mask, and stale state in a reused slot must null-route,
        # not corrupt the prompt's blocks mid-prefill.
        self._chunking: dict[int, dict] = {}
        self.metrics = metrics or ServeMetrics()
        self.tracer = tracer if tracer is not None else trace_mod.null_tracer()
        self.hb = heartbeat if heartbeat is not None \
            else hb_mod.null_heartbeat()
        self.chaos = chaos
        self.on_event = on_event
        # crash-safety + overload state (PR 8)
        self.journal = journal
        self._journal_err_reported = False
        self._draining = False
        self._drain_deadline: float | None = None
        self._unparsed = itertools.count()
        self._governor: BrownoutGovernor | None = None
        if cfg.brownout:
            depth_high = cfg.brownout_depth or max(
                1, (3 * cfg.queue_capacity) // 4)
            self._governor = BrownoutGovernor(
                depth_high=depth_high, wait_high_s=cfg.brownout_wait_s)
        # SLO burn-rate alerting (obs/slo.py): evaluated from the
        # serve loop (step AND idle ticks — an alert must be able to
        # clear while the engine sits idle after load drops) over the
        # windowed instruments the metrics layer already keeps.
        self.slo = None
        targets = slo_mod.standard_targets(
            cfg.slo_ttft_p99_ms, cfg.slo_reject_rate,
            cfg.slo_availability)
        if targets:
            self.slo = slo_mod.SLOMonitor(
                targets, self.metrics.reg,
                fast_s=cfg.slo_fast_s or slo_mod.DEFAULT_FAST_S,
                slow_s=cfg.slo_slow_s or slo_mod.DEFAULT_SLOW_S)
        self._slots: list[Request | None] = [None] * cfg.slots
        first_kind = self._first_kind = next(iter(self._kinds))
        pool_blocks = {k: cfg.slots * self._hold[k] + 1 for k in windowed}
        pool_blocks[first_kind] = num_blocks
        self._mgrs = {k: BlockManager(pool_blocks[k], bs)
                      for k in self._kinds}
        self._allocs: dict[str, list[SeqAlloc | None]] = {
            k: [None] * cfg.slots for k in self._kinds}
        self._bts = {k: np.zeros((cfg.slots, self._mb), np.int32)
                     for k in self._kinds}
        self.mgr = self._mgrs[first_kind]
        self._seqs = self._allocs[first_kind]
        self.prefix = RadixPrefixCache(self.mgr) if cfg.prefix_cache else None
        # tiered KV: the host-RAM spill tier behind the radix cache
        # (serve/hostcache.py) — eviction demotes, admission restores
        self.host: HostBlockStore | None = None
        self._hot_roots = HotRootTracker()
        if cfg.host_cache_mb > 0 and self.prefix is not None:
            self.host = HostBlockStore(cfg.host_cache_mb, bs)
            self.prefix.spill = self._spill_block
            if cfg.host_cache_dir:
                n_loaded = self.host.load(cfg.host_cache_dir)
                if n_loaded:
                    self.tracer.event(
                        "hostcache_loaded", chains=n_loaded,
                        mb=round(self.host.occupancy_mb, 3),
                        path=cfg.host_cache_dir)
            # publish occupancy from tick zero: `obs top` renders a
            # null gauge as tier-DISABLED, and an enabled-but-cold
            # tier must read 0.00/0M instead
            self.metrics.observe_host_cache(self.host.occupancy_mb)
        self._bt = self._bts[first_kind]
        self._bt_dev = None   # device mirror of (_bts, live); None = stale
        self._pending_reserve: dict[str, dict[str, int]] = {}
        self._order = itertools.count()
        # bytes of one block of each kind's pool, over that kind's layers
        self._kind_block_bytes = {
            k: paged_cache_block_bytes(mcfg, bs, kind=k)
            for k in self._kinds}
        self._block_bytes = self._kind_block_bytes[first_kind]
        self._cache = init_paged_cache(
            mcfg, pool_blocks if windowed else num_blocks, bs)
        self._state = self._init_state()
        self._tick_no = 0
        # cumulative transport-sink seconds (all requests); per-request
        # marks against this counter net decode gaps of EVERY sink
        # write in the window, not just the request's own — a slow
        # neighbour's client must not read as this slot's decode time
        self._sink_s = 0.0
        # introspection plane: compile ledger + host-tick profiler +
        # flight recorder (all host-only — none touch the device)
        self.ledger = CompileLedger()
        # every segment of a step is timed through this profiler and is
        # a span on the device profiler's clock meanwhile
        self.tickprof = TickProfiler(clock=_CLOCK, annotate=annotate)
        # a collection inside a step: the span `serve.step/gc`, `gc_us`
        self.tickprof.watch_collector()
        self.flight = (FlightRecorder(flight_path, run=self.tracer.run)
                       if flight_path else null_flight_recorder())
        self._prefill_tokens = 0  # padded tokens prefilled this step
        # rows of this step's decode tick that sample / that restrict
        self._sampling_rows = self._restricted_rows = 0
        # by layer kind, the blocks the paged-attention kernel walks in
        # this step's decode tick, a layer, and the table entries the
        # gather would have copied, under the tick record's names
        # (walked, entries): the full kind's bare, a windowed kind's
        # with its name behind
        def by_kind(names):
            return {k: tuple(f"{name}_{k}" if w else name for name in names)
                    for k, w in self._kinds.items()}

        self._walk_names = by_kind(WALK_COUNTERS)
        self._write_names = by_kind(WRITE_COUNTERS)
        self._no_walk = {
            name: 0 for names in self._walk_names.values() for name in names}
        self._walk_counted = self._no_walk
        # and, named the same way, how this step's calls put keys and
        # values into the pools (written by block, by row): summed over
        # the step's prefills, its chunk and its tick
        self._no_write = {
            name: 0 for names in self._write_names.values() for name in names}
        self._write_counted = dict(self._no_write)
        # an expert model: the static shape of its expert step, and the
        # (token, pick) rows this step's calls sent through each form
        # of the grouped products, summed over the expert layers
        # (`_count_experts`); nothing for a model without experts
        self._expert_step: dict | None = getattr(mcfg, "expert_step", None)
        self._no_expert_rows = {} if self._expert_step is None \
            else dict.fromkeys(EXPERT_ROW_COUNTERS, 0)
        self._expert_rows = dict(self._no_expert_rows)
        # the positions of this step's prefills and chunk, bucket
        # padding and all, by the read their windows took
        # (`_count_prompt`)
        self._prompt_positions = dict.fromkeys(PROMPT_READ_COUNTERS, 0)
        # what this step's decode tick counted on the device (an expert
        # model's picks: `_expert_counters`), fetched with its tokens
        self._tick_counted: dict[str, int] = {}
        self._loop_counted: dict[str, int] = {}
        self._last_prefill_bucket: int | None = None
        # `.nbytes` is shape metadata — summing it syncs nothing
        self._param_bytes = int(sum(
            getattr(x, "nbytes", 0)
            for x in jax.tree_util.tree_leaves(variables)))
        (self._tick_jit, self._prefill_jit, self._copy_jit,
         self._spec_jit, self._chunk_jit) = _shared_jits(
            donate=jax.default_backend() != "cpu")

    # ------------------------------------------------------ device state

    def _init_state(self) -> dict:
        S = self.cfg.slots
        return {
            "lengths": jnp.zeros((S,), jnp.int32),
            "active": jnp.zeros((S,), bool),
            "last_token": jnp.zeros((S,), jnp.int32),
            "generated": jnp.zeros((S,), jnp.int32),
            "budget": jnp.ones((S,), jnp.int32),
            "temperature": jnp.zeros((S,), jnp.float32),
            "top_k": jnp.zeros((S,), jnp.int32),
            "top_p": jnp.ones((S,), jnp.float32),
            "keys": jax.random.split(jax.random.key(0), S),
        }

    # --------------------------------------------------------- plumbing

    def bucket(self, prompt_len: int) -> int:
        """Smallest power-of-two >= prompt_len (floored at min_bucket,
        capped at max_len): the prefill jit compiles once per value
        this returns."""
        b = self.cfg.min_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.cfg.max_len)

    def compile_stats(self) -> dict:
        """Executable counts in the three jit caches — the no-recompile
        guarantee made measurable (tier-1 asserts these stay flat
        across slot churn, prefix hits, COW forks, and preemptions
        after `warmup`). The caches are PROCESS-wide (`_shared_jits`):
        engines over the same model and shapes share executables, so a
        second engine's warmup is free — counts only ever grow, and
        flatness between two readings still means "nothing traced"."""
        return {
            "tick_executables": self._tick_jit._cache_size(),
            "prefill_executables": self._prefill_jit._cache_size(),
            "copy_executables": self._copy_jit._cache_size(),
            "spec_tick_executables": self._spec_jit._cache_size(),
            "chunk_executables": self._chunk_jit._cache_size(),
        }

    def warmup(self, prompt_lens: list[int] | None = None) -> dict:
        """Compile the tick, the COW block copy, and one prefill per
        bucket, then reset serving state. The ladder covers EVERY
        bucket at or below the largest reachable suffix, not just the
        requested lengths: a prefix-cache hit shrinks a prompt to its
        suffix, which may land in any smaller bucket, and a hit must
        never cost a compile. Under `optimistic` admission the ladder
        extends all the way to max_len regardless of `prompt_lens`,
        because a pool-exhaustion preemption GROWS the prompt (the
        resume re-prefills prompt + generated) — O(log max_len)
        compiles, paid once. Under `reserve` admission nothing ever
        grows (the only requeue path fires before a token exists), so
        `prompt_lens` bounds the ladder."""
        want = self.bucket(max(prompt_lens or [self.cfg.min_bucket]))
        if self.cfg.admission == "optimistic":
            want = self.cfg.max_len
        if self.cfg.prefill_chunk > 0:
            # chunking caps every sampling prefill at the final segment
            # (suffix <= chunk), so the ladder stops at bucket(chunk)
            # no matter how long prompts get — resume growth under
            # optimistic admission included (a grown prompt just chunks
            # more segments)
            want = self.bucket(self.cfg.prefill_chunk)
        lens: list[int] = []
        b = self.cfg.min_bucket
        while True:
            pb = min(b, self.cfg.max_len)
            if pb not in lens:
                lens.append(pb)
            if pb >= want:
                break
            b *= 2
        compile_s: dict[str, float] = {}
        with self.tracer.span("serve_warmup") as sp:
            for pb in lens:
                dummy = Request(prompt_ids=np.ones((min(pb, 2),), np.int32),
                                max_new_tokens=2)
                # bt row is all-null during warmup: the dummy's writes
                # land in the garbage block, real state is untouched
                t0 = time.perf_counter()
                self._prefill_call(dummy, slot=0, bucket_len=pb)
                compile_s[f"prefill_b{pb}"] = round(
                    time.perf_counter() - t0, 4)
            t0 = time.perf_counter()
            _ = self._tick_device()
            compile_s["tick"] = round(time.perf_counter() - t0, 4)
            if self._spec:
                # the spec tick's one executable for this (S, k) —
                # all-zero drafts exercise the same shapes live
                # traffic will (acceptance is data, not shape)
                t0 = time.perf_counter()
                _ = self._spec_tick_device(
                    np.zeros((self.cfg.slots, self.cfg.spec_k), np.int32))
                compile_s["spec_tick"] = round(time.perf_counter() - t0, 4)
            if self.cfg.prefill_chunk > 0:
                # the chunk jit's ONE executable for this [1, C] window
                # — all-null bt row, so the dummy's K/V land in the
                # garbage block
                C = self.cfg.prefill_chunk
                t0 = time.perf_counter()
                self._cache = self._chunk_jit(
                    self.model, self.variables, self._cache,
                    jnp.full((1, C), self.cfg.pad_id, jnp.int32),
                    {k: jnp.zeros((self._mb,), jnp.int32)
                     for k in self._kinds}, jnp.int32(0))
                compile_s["chunk"] = round(time.perf_counter() - t0, 4)
            zero = self._in_every_segment([0])
            t0 = time.perf_counter()
            self._cache = self._copy_jit(self._cache, zero, zero)
            compile_s["copy"] = round(time.perf_counter() - t0, 4)
            sp.set(buckets=lens)
        self._state = self._init_state()
        self._slots = [None] * self.cfg.slots
        for k in self._kinds:
            self._allocs[k][:] = [None] * self.cfg.slots
            self._bts[k][:] = 0
        self._chunking = {}
        self._bt_dev = None
        stats = self.compile_stats()
        total_s = round(sp.dur_s or 0.0, 4)
        self.ledger.record_warmup(stats, compile_s=compile_s,
                                  total_s=total_s)
        self.ledger.set_baseline(stats)
        self.tracer.event("serve_warmup_done", **stats)
        self.tracer.event("compile_ledger", total_s=total_s,
                          compile_s=compile_s, **stats)
        return stats

    def _prefill_call(self, req: Request, slot: int, *, start: int = 0,
                      prompt: np.ndarray | None = None,
                      budget: int | None = None,
                      bucket_len: int | None = None,
                      seg: str = "admit"):
        """One prefill: the prompt's upload, the dispatch and the fetch
        of its first token, each a child of the step segment `seg` that
        names the bucket and the prefix hit it ran with."""
        ids = req.prompt_ids if prompt is None else prompt
        suffix = ids[start:]
        P = int(suffix.shape[0])
        Pb = bucket_len or self.bucket(P)
        self._last_prefill_bucket = Pb   # churn context for the ledger
        self._prefill_tokens += Pb
        self._count_write(
            {k: t[slot] for k, t in self._bts.items()}, start, Pb)
        self._count_experts(Pb)
        self._count_prompt(Pb)
        prof, at = self.tickprof, {"bucket": Pb, "start": start}
        with prof.seg(f"{seg}/upload", **at):
            buf = np.full((1, Pb), self.cfg.pad_id, np.int32)
            buf[0, :P] = suffix
            args = (
                jnp.asarray(buf), self._rows_on_device(slot),
                jnp.int32(slot), jnp.int32(start), jnp.int32(P),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.float32(req.top_p),
                jnp.int32(req.max_new_tokens if budget is None else budget),
                jax.random.key(req.seed),
            )
        with prof.seg(f"{seg}/dispatch", **at):
            self._cache, self._state, first, finished = self._prefill_jit(
                self.model, self.cfg.eos_id,
                self.variables, self._cache, self._state, *args)
        # an array a wait, in the order the host waits: the first holds
        # the program's run, the second a program that has ended
        with prof.seg(f"{seg}/fetch", **at):
            with prof.seg(f"{seg}/fetch/tokens"):
                first = int(first)
            with prof.seg(f"{seg}/fetch/finished"):
                finished = bool(finished)
            return first, finished

    def _live_mask(self) -> np.ndarray:
        """Slots the decode tick may advance: occupied AND not mid-
        chunk. A chunking slot's device row is zeroed and its state
        rows are a previous occupant's leftovers — the mask (plus the
        zeroed row, belt and braces) keeps the tick from decoding
        garbage into it."""
        return np.fromiter(
            (r is not None and s not in self._chunking
             for s, r in enumerate(self._slots)),
            bool, len(self._slots))

    def _count_write(self, rows: dict, start: int, T: int) -> None:
        """Add to the step's write counters what a call that writes the
        `T` positions from `start` through a slot's table `rows` (by
        kind) puts into each kind's pool, a layer: whole blocks where
        `paged_kv_write` will take its block path (the program asks the
        same question of the same numbers), else positions row by row;
        either way only what lands in a mapped block (bucket padding
        past the chain and blocks a windowed kind let go land nowhere
        that counts). Host arithmetic."""
        from hyperion_tpu.models.llama import kv_write_by_block

        bs = self.cfg.block_size
        by_block = kv_write_by_block(T, bs, np.int32(start))
        lo, hi = start // bs, min(blocks_for(start + T, bs), self._mb)
        span = np.arange(lo, hi)
        # positions of the window inside each block it touches
        held = np.minimum(start + T, (span + 1) * bs) \
            - np.maximum(start, span * bs)
        for k, row in rows.items():
            blocks, positions = self._write_names[k]
            live = np.asarray(row[lo:hi]) != 0
            if by_block:
                self._write_counted[blocks] += int(live.sum())
            else:
                self._write_counted[positions] += int(held[live].sum())

    def _count_experts(self, tokens: int) -> None:
        """Add to the step's `expert_rows_kernel` / `expert_rows_ragged`
        the (token, pick) rows a call over `tokens` positions sends
        through the expert step, every expert layer, under the form
        `select_grouped_impl` names for its shape: the question the
        program itself asked at trace time. Host arithmetic."""
        if self._expert_step is None:
            return
        from hyperion_tpu.ops import moe

        e = self._expert_step
        rows = tokens * e["top_k"]
        form = moe.select_grouped_impl(
            rows, e["groups"], e["k"], e["n"], jax.default_backend(),
            e["itemsize"])
        self._expert_rows[f"expert_rows_{form}"] += e["layers"] * rows

    def _count_prompt(self, positions: int) -> None:
        """Add a prefill's or a chunk's window of `positions` to the
        step's `prompt_positions_tiled` or `prompt_positions_gather`,
        by the read the model's selector names for a window that wide
        (`llama.select_paged_attn_impl`, or the model's explicit
        choice): the question the program asked at trace time. A window
        read in place ("pallas": a bucket no wider than a verify
        window, or a model told to read every window so) adds to
        neither. Host arithmetic."""
        name = f"prompt_positions_{self._read_for(positions)}"
        if name in self._prompt_positions:
            self._prompt_positions[name] += positions

    def _count_walk(self) -> dict[str, int]:
        """The tick record's walk counters for the tick about to be
        dispatched, by layer kind (`_no_walk`'s names): the entries a
        gather copies of the kind's table a layer, and, where the tick
        reads in place, the blocks the kernel's loops visit a layer,
        from the lengths it will read: a live slot's chain from the
        block of the first position its query sees (block 0 of a full
        kind) to the block of its window's last position, one (null)
        block of a lane the tick masks out. Host arithmetic."""
        bs, width = self.cfg.block_size, self._tick_width
        counted = dict(self._no_walk)
        for k, (_, entries) in self._walk_names.items():
            counted[entries] = self.cfg.slots * self._view[k]
        if self._tick_read == "pallas":
            live = [self._seqs[s].n_filled
                    for s, on in enumerate(self._live_mask()) if on]
            for k, w in self._kinds.items():
                counted[self._walk_names[k][0]] = sum(
                    min(self._mb, blocks_for(n + width, bs))
                    - (max(n - w + 1, 0) // bs if w else 0)
                    for n in live) + self.cfg.slots - len(live)
        return counted

    def _rows_on_device(self, slot: int | None = None,
                        rows: dict | None = None) -> dict:
        """The block tables by kind as the jits take them: every slot's
        (the tick), one slot's rows (its prefill), or rows held aside
        (a chunking slot's)."""
        if rows is None:
            rows = self._bts if slot is None else \
                {k: t[slot] for k, t in self._bts.items()}
        return {k: jnp.asarray(t) for k, t in rows.items()}

    def _tables_on_device(self) -> tuple:
        if self._bt_dev is None:
            # upload only when the table or slot liveness changed —
            # steady-state decode re-uses the device copies, so a tick
            # costs zero host->device traffic. A segment of its own:
            # `device` is the call's wall net of it
            with self.tickprof.seg("bt_upload"):
                self._bt_dev = (self._rows_on_device(),
                                jnp.asarray(self._live_mask()))
        return self._bt_dev

    def _tick_device(self):
        prof = self.tickprof
        tables = self._tables_on_device()
        with prof.seg("device/dispatch"):
            self._cache, self._state, toks, fins, counted = self._tick_jit(
                self.model, self.cfg.eos_id, self.cfg.pad_id,
                self.variables, self._cache, self._state, *tables)
        # the host fetch is the fence: tick spans time real work
        with prof.seg("device/fetch"):
            for x in counted.values():
                # rides the tokens' fetch: on the host by the time they are
                x.copy_to_host_async()
            with prof.seg("device/fetch/tokens"):
                toks = np.asarray(toks)
            with prof.seg("device/fetch/finished"):
                fins = np.asarray(fins)
            if counted:
                with prof.seg("device/fetch/counters"):
                    self._tick_counted = {
                        k: int(v) for k, v in counted.items()}
            return toks, fins

    def _collect_drafts(self) -> np.ndarray:
        """[S, spec_k] proposals for this tick, one drafter call per
        live slot over its visible context — host-side only, shipped
        with the tick like the block table. Dead lanes stay zero (the
        tick masks them out anyway)."""
        k = self.cfg.spec_k
        drafts = np.zeros((self.cfg.slots, k), np.int32)
        for s, req in enumerate(self._slots):
            if req is not None and s not in self._chunking:
                drafts[s] = self._drafter.propose(
                    s, req.prompt_ids, req.tokens, k)
        return drafts

    def _spec_tick_device(self, drafts: np.ndarray):
        prof = self.tickprof
        tables = self._tables_on_device()
        with prof.seg("device/dispatch"):
            self._cache, self._state, out, cnt, acc, fins = self._spec_jit(
                self.model, self.cfg.eos_id, self.cfg.pad_id,
                self.variables, self._cache, self._state, *tables,
                jnp.asarray(drafts))
        with prof.seg("device/fetch"):
            fetched = []
            for name, x in (("tokens", out), ("counts", cnt),
                            ("accepted", acc), ("finished", fins)):
                with prof.seg(f"device/fetch/{name}"):
                    fetched.append(np.asarray(x))
            return tuple(fetched)

    # --------------------------------------------------- block plumbing

    def _effective(self, req: Request) -> tuple[np.ndarray, int]:
        """(prompt, remaining budget) — preemption-aware: a preempted
        request resumes by prefilling prompt + everything it already
        generated (recompute preemption), which reproduces the exact
        decode state it lost."""
        if req.tokens:
            prompt = np.concatenate(
                [req.prompt_ids, np.asarray(req.tokens, np.int32)])
            return prompt, req.max_new_tokens - len(req.tokens)
        return req.prompt_ids, req.max_new_tokens

    def _block_demand(self, req: Request) -> dict[str, int]:
        """Exclusive new blocks this request needs of each layer kind's
        pool — worst-case span under `reserve` admission, prompt-only
        under `optimistic` — net of blocks a radix hit would share. A
        windowed kind never holds more than `_hold` blocks of a slot at
        a time, however long the span."""
        prompt, budget = self._effective(req)
        P = int(prompt.shape[0])
        bs = self.cfg.block_size
        reserve = self.cfg.admission == "reserve"
        need = {}
        for kind, window in self._kinds.items():
            if window:
                piece = min(P, self.cfg.prefill_chunk or P)
                need[kind] = min(
                    self._hold[kind],
                    blocks_for(P + budget if reserve else piece, bs))
            else:
                need[kind] = blocks_for(P + budget if reserve else P, bs)
        if self.prefix is not None:
            need[self._first_kind] -= len(
                self.prefix.lookup(prompt, P - 1).blocks)
        return need

    def _can_admit(self, req: Request) -> bool:
        """Block-availability gate for the queue: pop only when the
        demand is covered, in every kind's pool, by free +
        evictable-radix blocks, net of reservations already promised
        to in-flight requests. Covered demand is reserved immediately
        (released as real blocks are claimed), so one scheduling round
        cannot double-spend."""
        need = self._block_demand(req)
        evictable = self.prefix.evictable() if self.prefix else 0
        for kind, n in need.items():
            mgr = self._mgrs[kind]
            if n > mgr.num_free + evictable - mgr.reserved:
                return False
            evictable = 0       # the trie holds blocks of the first kind
        for kind, n in need.items():
            self._mgrs[kind].reserve(n)
        self._pending_reserve[req.id] = need
        return True

    def _release_pending(self, rid: str) -> None:
        """Give back what the gate reserved for a popped request."""
        for kind, n in self._pending_reserve.pop(rid, {}).items():
            self._mgrs[kind].release(n)

    def _alloc(self, n: int, seq: SeqAlloc | None = None,
               kind: str | None = None) -> list[int] | None:
        """Allocation from a kind's pool (the first kind's by default,
        with radix eviction backing); claims against `seq`'s
        reservation when it holds one."""
        mgr = self.mgr if kind is None else self._mgrs[kind]
        blocks = mgr.alloc(n)
        if blocks is None and self.prefix is not None and mgr is self.mgr:
            freed = self.prefix.evict(n - mgr.num_free)
            if freed:
                self.metrics.on_evict(freed)
            blocks = mgr.alloc(n)
        if blocks is not None and seq is not None and seq.reserved:
            take = min(seq.reserved, n)
            seq.reserved -= take
            mgr.release(take)
        return blocks

    def _slide_windows(self, slot: int, upto: int | None = None,
                       rows: dict | None = None) -> bool:
        """A windowed kind's chain follows its slot: the blocks that
        fell behind the window of every query still to come (queries
        start at the write frontier `n_filled`) go back to the free
        list now, and with `upto` the chain then owns blocks for every
        position below it (the next prefill piece). `rows`: the table
        rows to keep in step, a chunking slot's held-aside ones; the
        live table's by default. False when a pool ran dry."""
        bs = self.cfg.block_size
        n = self._seqs[slot].n_filled
        for kind, window in self._kinds.items():
            if not window:
                continue
            seq, mgr = self._allocs[kind][slot], self._mgrs[kind]
            row = self._bts[kind][slot] if rows is None else rows[kind]
            dead = max(0, n - window + 1) // bs - seq.first
            if dead > 0:
                mgr.decref(seq.blocks[:dead])
                del seq.blocks[:dead]
                row[seq.first:seq.first + dead] = 0
                seq.first += dead
                if self.cfg.admission == "reserve":
                    # what it let go it may need again further on, up
                    # to its hold or the end of its span
                    again = min(self._hold[kind], seq.limit - seq.first) \
                        - len(seq.blocks) - seq.reserved
                    seq.reserved += again
                    mgr.reserve(again)
                self._bt_dev = None
            if upto is not None:
                more = blocks_for(upto, bs) - seq.first - len(seq.blocks)
                if more > 0:
                    got = self._alloc(more, seq, kind)
                    if got is None:
                        return False
                    at = seq.first + len(seq.blocks)
                    row[at:at + more] = got
                    seq.blocks.extend(got)
                    self._bt_dev = None
        return True

    def _in_every_segment(self, blocks) -> jax.Array:
        """Block ids as the pools hold them: of a model whose pool
        arrays hold `cache_segments` caches one id a segment,
        segment-major (segment `s` starts at `s * num_blocks`:
        `init_paged_cache`); of every other model the ids themselves."""
        ids = np.asarray(blocks, np.int32)
        starts = self.cfg.num_blocks * np.arange(
            self._segments, dtype=np.int32)
        return jnp.asarray((starts[:, None] + ids[None, :]).reshape(-1))

    def _spill_block(self, chain_tokens: tuple[int, ...],
                     block: int) -> None:
        """Radix eviction's demotion hook (blocks.py `_drop`): read the
        dying block's K/V out of the device pool into one stacked host
        array and hand it to the host tier keyed by its full chain
        prefix. Eager per-layer D2H reads — none of the engine's
        tracked jits are involved, so `compile_stats()` stays flat."""
        ids = self._in_every_segment([block])
        payload = np.stack([
            np.stack([np.asarray(layer["k"][ids]),
                      np.asarray(layer["v"][ids])], axis=1)
            for layer in self._cache])  # [pools, segments, 2, H, bs, D]
        # a cache layer an entry
        payload = payload.reshape(-1, *payload.shape[2:])
        if self.host.put(chain_tokens, payload):
            self.metrics.on_host_spill(payload.nbytes)
            self.metrics.observe_host_cache(self.host.occupancy_mb)

    def _restore_blocks(self, blocks: list[int],
                        payloads: list[np.ndarray]) -> int:
        """The promotion half: scatter spilled host payloads into
        freshly allocated device blocks — one device_put + `.at[].set`
        block-scatter per layer, eager (never a tracked jit), and the
        D2H/H2D round trip in the pool's own dtype is bit-exact, so a
        restored stream matches the never-evicted run. Returns bytes
        moved."""
        ids = self._in_every_segment(blocks)    # segment-major
        stacked = np.stack(payloads)  # [n, pools * segments, 2, H, bs, D]
        moved = int(stacked.nbytes)
        n, segs = len(blocks), self._segments
        dev = jax.device_put(stacked)
        # [pools, 2, segments * n, H, bs, D]: a pool's blocks in `ids`' order
        dev = dev.reshape(n, -1, segs, *dev.shape[2:]).transpose(
            1, 3, 2, 0, 4, 5, 6).reshape(-1, 2, segs * n, *dev.shape[3:])
        self._cache = [
            {"k": layer["k"].at[ids].set(dev[li, 0]),
             "v": layer["v"].at[ids].set(dev[li, 1])}
            for li, layer in enumerate(self._cache)
        ]
        return moved

    def _free_slot(self, slot: int) -> None:
        for kind, mgr in self._mgrs.items():
            seq = self._allocs[kind][slot]
            if seq is not None:
                mgr.release(seq.reserved)
                mgr.decref(seq.blocks)
            self._allocs[kind][slot] = None
            self._bts[kind][slot, :] = 0
        self._slots[slot] = None
        self._chunking.pop(slot, None)
        self._bt_dev = None

    def _claim_blocks(self, req: Request):
        """The block half of an admission: radix lookup, host-tier
        probe, pins, allocation, copy-on-write fork and restore. Returns
        (prompt, budget, start, seqs), `start` the prefix hit in tokens
        and `seqs` the slot's chain of each layer kind, or None when
        allocation lost a race. Each stage that runs is a child of the
        caller's `admit/blocks`."""
        prof = self.tickprof
        prompt, budget = self._effective(req)
        P = int(prompt.shape[0])
        bs = self.cfg.block_size
        shared: list[int] = []
        cow_src: int | None = None
        start = 0
        host_payloads: list[np.ndarray] = []
        device_start = 0
        if self.prefix is not None:
            with prof.seg("admit/blocks/lookup"):
                m = self.prefix.lookup(prompt, P - 1)
                shared, start, cow_src = m.blocks, m.tokens, m.cow_src
                device_start = start
                if self.host is not None:
                    # device-miss -> host-hit fall-through: probe the
                    # host tier for full-block chain links beyond the
                    # device match. A host extension only wins when it
                    # covers MORE than the device walk (its mid-block COW
                    # extension included) — then the restore supersedes
                    # the COW copy.
                    base = len(shared) * bs
                    host_payloads = self.host.match(prompt, base, P - 1)
                    if host_payloads \
                            and base + len(host_payloads) * bs > start:
                        start = base + len(host_payloads) * bs
                        cow_src = None
                    else:
                        host_payloads = []
        # radix eviction, where the pool is short, is inside `_alloc`
        with prof.seg("admit/blocks/alloc_evict"):
            need_now = blocks_for(P, bs) - len(shared)
            # pin the matched chain (and the COW source) BEFORE
            # allocating: allocation may evict radix holds, and a
            # trie-only block we just matched is exactly what LRU
            # eviction would pick off
            pin = shared + ([cow_src] if cow_src is not None else [])
            self.mgr.incref(pin)
            fresh = self._alloc(need_now) if need_now else []
            # a windowed kind starts with the blocks of the first piece
            # the prefill writes; `_slide_windows` moves the chain on
            # from there
            piece = blocks_for(min(P, self.cfg.prefill_chunk or P), bs)
            windows = {k: self._mgrs[k].alloc(piece)
                       for k, w in self._kinds.items() if w} \
                if fresh is not None else {}
            if fresh is None or None in windows.values():
                self.mgr.decref(pin + (fresh or []))
                for k, got in windows.items():
                    self._mgrs[k].decref(got or [])
                self._release_pending(req.id)
                return None
        # Re-derive the growth reservation instead of netting the
        # gate's estimate against need_now: an earlier admission this
        # round may have evicted blocks the gate counted as shared, and
        # growth demand — blocks_for(P+budget) - blocks_for(P) — does
        # not depend on sharing at all, so computing it directly keeps
        # the reserve-mode "exhaustion impossible" ledger exact even
        # when the gate's sharing estimate went stale.
        with prof.seg("admit/blocks/reserve"):
            self._release_pending(req.id)
            growth = 0
            span = blocks_for(P + budget, bs)
            if self.cfg.admission == "reserve":
                growth = span - blocks_for(P, bs)
                self.mgr.reserve(growth)
            seq = SeqAlloc(
                blocks=shared + fresh, n_shared=len(shared),
                reserved=growth, order=next(self._order),
            )
            seqs = {self._first_kind: seq}
            for k, got in windows.items():
                ahead = min(self._hold[k], span) - piece \
                    if self.cfg.admission == "reserve" else 0
                self._mgrs[k].reserve(ahead)
                seqs[k] = SeqAlloc(blocks=got, reserved=ahead, limit=span)
        if cow_src is not None:
            # mid-block divergence: duplicate the agreeing block so our
            # writes (suffix prefill + decode) never touch the shared
            # original — the copy-on-write half of the design
            with prof.seg("admit/blocks/cow"):
                self._cache = self._copy_jit(
                    self._cache, self._in_every_segment([cow_src]),
                    self._in_every_segment([fresh[0]]))
                self.mgr.decref([cow_src])  # the pin; the copy is ours now
                self.metrics.on_cow()
        if host_payloads:
            # promote the matched chain out of the host tier: the first
            # len(host_payloads) fresh blocks are exactly the logical
            # positions after the device-shared span, so the scatter
            # lands them where the block table will address them. The
            # post-prefill `prefix.insert` re-registers the whole chain
            # (restored blocks included) in the radix, so the prefix is
            # device-cached again for the next sharer.
            with prof.seg("admit/blocks/restore"):
                moved = self._restore_blocks(
                    fresh[:len(host_payloads)], host_payloads)
                host_tokens = len(host_payloads) * bs
                self.metrics.on_host_restore(len(host_payloads), moved)
                self.metrics.observe_host_cache(self.host.occupancy_mb)
                self.tracer.event(
                    "host_restore", request=req.id, tick=self._tick_no,
                    blocks=len(host_payloads), tokens=host_tokens,
                    bytes=moved, **_tr(req))
        if self.prefix is not None:
            with prof.seg("admit/blocks/account"):
                self.metrics.on_prefix_lookup(P, start)
                # tier attribution: under a host hit the device's share
                # is the full-block walk (the superseded COW extension
                # never ran), so device + host sum to exactly `start`
                self.metrics.on_tier_lookup(
                    device_tokens=len(shared) * bs if host_payloads
                    else device_start,
                    host_tokens=len(host_payloads) * bs)
                self._hot_roots.note(prefix_root_digest(prompt))
        return prompt, budget, start, seqs

    def _admit(self, req: Request, slot: int) -> TokenEvent | None:
        """Prefill `req` into `slot` through the paged pool: radix
        lookup -> share/COW -> allocate exclusives -> prefill the
        suffix -> register prompt blocks. Returns the first-token
        event, or None when allocation lost a race (caller requeues)."""
        with self.tickprof.seg("admit/blocks"):
            claimed = self._claim_blocks(req)
        if claimed is None:
            return None
        prompt, budget, start, seqs = claimed
        seq = seqs[self._first_kind]
        P = int(prompt.shape[0])
        resumed = req.first_token_at is not None
        C = self.cfg.prefill_chunk
        if C > 0 and P - start > C:
            # chunked prefill: the suffix is too long for one segment.
            # Claim the slot and its blocks NOW (the gate already
            # reserved them), but hold the real block-table row ASIDE
            # and keep the device row zeroed — the decode tick writes
            # K/V at lengths[slot] for ALL lanes and this slot's device
            # state still belongs to a previous occupant, so its writes
            # must null-route until the final segment installs real
            # state. `_advance_chunks` runs one [1, C] segment per step
            # between decode ticks; the prefix is NOT registered in the
            # radix until the blocks actually hold it.
            rows = {}
            for k, q in seqs.items():
                rows[k] = np.zeros((self._mb,), np.int32)
                rows[k][:len(q.blocks)] = q.blocks
                self._bts[k][slot, :] = 0
                self._allocs[k][slot] = q
            self._bt_dev = None
            seq.n_filled = start
            self._slots[slot] = req
            self._chunking[slot] = {
                "req": req, "prompt": prompt, "budget": budget,
                "pos": start, "rows": rows, "resumed": resumed,
            }
            self.tracer.event(
                "prefill_chunked", request=req.id, tick=self._tick_no,
                slot=slot, prompt_len=P, cached_tokens=start, chunk=C,
                segments=-(-(P - start) // C), resumed=resumed,
                **_tr(req))
            return _CHUNK_ADMIT
        for k, q in seqs.items():
            self._bts[k][slot, :len(q.blocks)] = q.blocks
            self._bts[k][slot, len(q.blocks):] = 0
            # installed before the prefill: a slot that finishes on its
            # first token is freed through these
            self._allocs[k][slot] = q
        self._bt_dev = None
        with self.tracer.span("serve_prefill", step=self._tick_no) as sp:
            first, finished = self._prefill_call(
                req, slot, start=start, prompt=prompt, budget=budget)
            sp.set(request=req.id, slot=slot, prompt_len=P,
                   cached_tokens=start, bucket=self.bucket(P - start),
                   resumed=resumed)
        seq.n_filled = P
        if self.prefix is not None:
            self.prefix.insert(prompt, seq.blocks)
        now = _CLOCK()
        req.prefilled_at = now
        if resumed:
            # a resume re-prefills prompt + generated: pure replay cost
            req.replay_s += sp.dur_s or 0.0
        else:
            req.prefill_s += sp.dur_s or 0.0
        if not resumed:
            req.first_token_at = now
            self.metrics.on_first_token(req, now)
            self.tracer.event(
                "request_first_token", request=req.id, tick=self._tick_no,
                ttft_s=round(now - req.submitted_at, 6),
                queue_wait_s=round(req.queue_wait_s, 6),
                gate_wait_s=round(req.gate_wait_s, 6),
                prefill_s=round(req.prefill_s, 6), **_tr(req))
        else:
            gap_from = getattr(req, "_last_emit_at", None)
            if gap_from is not None:
                self.metrics.on_token_gap(now - gap_from, req.sla_class)
        req._last_emit_at = now
        req._sink_mark = self._sink_s
        self.metrics.count_tokens(1)  # the prefill-sampled token
        self._slots[slot] = req
        if finished:
            self._free_slot(slot)
        else:
            self._slide_windows(slot)
        return TokenEvent(req, first, finished)

    def _advance_chunks(self) -> list[TokenEvent]:
        """Run at most ONE prefill segment this step — the oldest
        chunking slot's — so long prompts interleave with decode ticks
        instead of stalling them (Sarathi-Serve's stall-free schedule).
        Intermediate segments go through the chunk jit (K/V only, no
        sampling); the final segment (suffix <= chunk, so its bucket is
        already on the warmup ladder) runs the normal sampling prefill
        with `start` at the chunk boundary — the fold position is the
        total prompt length - 1 either way, so the first token is
        bit-identical to a one-shot prefill."""
        if not self._chunking:
            return []
        C = self.cfg.prefill_chunk
        slot = min(self._chunking, key=lambda s: self._seqs[s].order)
        ck = self._chunking[slot]
        req, prompt, budget = ck["req"], ck["prompt"], ck["budget"]
        P = int(prompt.shape[0])
        pos = ck["pos"]
        # the windowed kinds' chains move on to this piece: its blocks
        # come (a pool that ran dry, under optimistic admission, sends
        # the request back instead); what falls behind goes as soon as
        # the piece is written
        if not self._slide_windows(slot, min(P, pos + C), ck["rows"]):
            self._preempt(slot)
            return []
        if P - pos > C:
            prof, at = self.tickprof, {"bucket": C, "start": pos}
            self._prefill_tokens += C
            self._count_write(ck["rows"], pos, C)
            self._count_experts(C)
            self._count_prompt(C)
            t0 = _CLOCK()
            with prof.seg("chunk/upload", **at):
                args = (jnp.asarray(np.asarray(prompt[pos:pos + C],
                                               np.int32)[None, :]),
                        self._rows_on_device(rows=ck["rows"]),
                        jnp.int32(pos))
            with prof.seg("chunk/dispatch", **at):
                self._cache = self._chunk_jit(
                    self.model, self.variables, self._cache, *args)
            # fence: the segment's wall time must land in THIS step's
            # chunk segment, not smear into the next device call
            with prof.seg("chunk/fetch", **at):
                jax.block_until_ready(self._cache)
            dt = _CLOCK() - t0   # the request's own account, not the step's
            if ck["resumed"]:
                req.replay_s += dt
            else:
                req.prefill_s += dt
            ck["pos"] = pos + C
            self._seqs[slot].n_filled = pos + C
            self._slide_windows(slot, rows=ck["rows"])
            return []
        # final segment: install the real row — `_prefill_impl` sets
        # every state field for this slot via `.at[slot].set`, so the
        # stale-lane hazard ends here
        for k, row in ck["rows"].items():
            self._bts[k][slot, :] = row
        self._bt_dev = None
        del self._chunking[slot]
        resumed = ck["resumed"]
        with self.tracer.span("serve_prefill", step=self._tick_no) as sp:
            first, finished = self._prefill_call(
                req, slot, start=pos, prompt=prompt, budget=budget,
                seg="chunk")
            sp.set(request=req.id, slot=slot, prompt_len=P,
                   cached_tokens=pos, bucket=self.bucket(P - pos),
                   resumed=resumed, chunked=True)
        seq = self._seqs[slot]
        seq.n_filled = P
        if self.prefix is not None:
            self.prefix.insert(prompt, seq.blocks)
        now = _CLOCK()
        req.prefilled_at = now
        if resumed:
            req.replay_s += sp.dur_s or 0.0
        else:
            req.prefill_s += sp.dur_s or 0.0
        if not resumed:
            req.first_token_at = now
            self.metrics.on_first_token(req, now)
            self.tracer.event(
                "request_first_token", request=req.id, tick=self._tick_no,
                ttft_s=round(now - req.submitted_at, 6),
                queue_wait_s=round(req.queue_wait_s, 6),
                gate_wait_s=round(req.gate_wait_s, 6),
                prefill_s=round(req.prefill_s, 6), chunked=True,
                **_tr(req))
        else:
            gap_from = getattr(req, "_last_emit_at", None)
            if gap_from is not None:
                self.metrics.on_token_gap(now - gap_from, req.sla_class)
        req._last_emit_at = now
        req._sink_mark = self._sink_s
        self.metrics.count_tokens(1)  # the prefill-sampled token
        if finished:
            self._free_slot(slot)
        else:
            self._slide_windows(slot)
        return [TokenEvent(req, first, finished)]

    def _preempt(self, slot: int, reason: str = "pool_exhausted") -> None:
        """Push this slot's request back to the queue HEAD (recompute
        preemption — generated tokens ride along and re-prefill on
        re-admission, often from their own radix-cached prefix). Fires
        on pool exhaustion and on preempt-batch-for-interactive (a
        block-gated interactive head evicting the youngest batch slot).
        The degraded-but-alive alternative to a crash."""
        req = self._slots[slot]
        self._free_slot(slot)
        self.metrics.on_preempt()
        req.preempts += 1
        req._preempted = True  # its next queue wait is replay, not FIFO
        self.tracer.event("request_preempted", request=req.id,
                          generated=len(req.tokens), tick=self._tick_no,
                          reason=reason, sla_class=req.sla_class,
                          **_tr(req))
        self.queue.push_front(req)

    def _account_pop(self, req) -> bool:
        """Bank the queue wait that ended at this pop into its
        attribution bucket: replay wait when the pop resumes a
        preemption, otherwise FIFO wait with the block-gated tail
        (stamped by `pop_ready` at the first denial) broken out.
        Returns whether this pop was a preemption resume, so a caller
        that requeues the request (allocation race) can restore the
        flag — the request is STILL a resume and its next wait must
        bank as replay, not FIFO queue_wait."""
        popped = (req.admitted_at if req.admitted_at is not None
                  else _CLOCK())
        wait = max(0.0, popped - req.enqueued_at)
        gate = 0.0
        if req.gate_blocked_at is not None:
            gate = min(wait, max(0.0, popped - req.gate_blocked_at))
            req.gate_blocked_at = None
        resumed = req._preempted
        if resumed:
            req._preempted = False
            req.replay_s += wait
        else:
            req.gate_wait_s += gate
            req.queue_wait_s += wait - gate
        if self._governor is not None:
            # every completed wait (replay stints included — congestion
            # is congestion) feeds the brownout p95 window, tagged with
            # its class so shed_doomed can estimate per-class
            self._governor.observe_wait(wait, req.sla_class)
        self.tracer.event(
            "request_scheduled", request=req.id, tick=self._tick_no,
            resumed=resumed,
            queue_wait_s=round(0.0 if resumed else wait - gate, 6),
            gate_wait_s=round(0.0 if resumed else gate, 6),
            replay_wait_s=round(wait if resumed else 0.0, 6),
            **_tr(req))
        return resumed

    def _ensure_blocks(self) -> None:
        """Before a tick, every live slot must own the block its next
        write lands in. Allocate (evicting radix holds as needed);
        when the pool is truly dry, preempt the YOUNGEST slot and
        retry — oldest requests always progress, so the loop
        terminates and nobody starves."""
        for s in sorted(
            # a chunking slot writes through rows held aside, and
            # `_advance_chunks` sees to its blocks
            (t for t in range(self.cfg.slots)
             if self._slots[t] is not None and t not in self._chunking),
            key=lambda t: self._seqs[t].order,
        ):
            for kind in self._kinds:
                while self._slots[s] is not None:
                    seq = self._allocs[kind][s]
                    lookahead = 0
                    if self._spec:
                        # the verify window writes positions n_filled ..
                        # n_filled+k, but only positions an ACCEPTED token
                        # can land in need real blocks (acceptance is
                        # capped by the remaining budget; writes past the
                        # table's chain null-route harmlessly) — so the
                        # lookahead never exceeds the worst-case span the
                        # reserve-mode ledger already accounts for
                        req = self._slots[s]
                        lookahead = max(0, min(
                            self.cfg.spec_k,
                            req.max_new_tokens - len(req.tokens) - 1))
                    needed = (self._seqs[s].n_filled + lookahead) \
                        // self.cfg.block_size + 1
                    if seq.first + len(seq.blocks) >= needed:
                        break
                    got = self._alloc(1, seq, kind)
                    if got is not None:
                        self._bts[kind][
                            s, seq.first + len(seq.blocks)] = got[0]
                        seq.blocks.append(got[0])
                        self._bt_dev = None
                        continue
                    live = [t for t in range(self.cfg.slots)
                            if self._slots[t] is not None]
                    # batch absorbs pool pressure first: evict the
                    # youngest batch slot when one exists, the youngest
                    # overall otherwise (the starvation-freedom argument —
                    # oldest always progresses — is unchanged either way)
                    batch = [t for t in live
                             if self._slots[t].sla_class == CLASS_BATCH]
                    victim = max(batch or live,
                                 key=lambda t: self._seqs[t].order)
                    self._preempt(victim)

    # ------------------------------------------------------------ events

    def _journal_guard(self) -> None:
        """Surface a journal IO failure exactly once: the engine keeps
        serving (durability degraded beats dead), but the stream and
        the counters must say so — a silent WAL loss would read as
        crash-safe right up to the crash."""
        j = self.journal
        if j is not None and not j.enabled and not self._journal_err_reported:
            self._journal_err_reported = True
            self.metrics.on_journal_error()
            self.tracer.event("journal_io_error", error=j.error)
            print(f"[serve] journal disabled after IO error: {j.error} — "
                  "serving continues WITHOUT crash recovery",
                  file=sys.stderr)

    def _emit(self, ev: TokenEvent) -> None:
        req = ev.request
        if ev.kind == "token" and ev.token is not None:
            req.tokens.append(ev.token)
        if ev.finished and ev.kind == "token":
            req.status = "done"
        # Journal BEFORE the sink write, flushed to the kernel inside
        # `token`/`finish` (serve/journal.py's ordering contract): any
        # token a client ever received is already durable, so a replay
        # can never re-compute — hence never re-deliver — it. The
        # client stream stays duplicate-free across kills.
        # token AND timeout emissions happen only on the engine thread
        # inside step(): those alone enter the step's record (its
        # `journal` and `sink` segments, netted out of whichever segment
        # they happen in). Reject writes run on front-end reader threads
        # in parallel with ticks: a span, never the record
        on_step = ev.kind in ("token", "timed_out")
        if self.journal is not None and req._journaled:
            with self.tickprof.seg("journal", record=on_step):
                if ev.kind == "token" and ev.token is not None:
                    self.journal.token(req.id, ev.token)
                if ev.finished:
                    self.journal.finish(
                        req.id,
                        "done" if ev.kind in ("token", "done")
                        else (ev.reason or ev.kind))
            self._journal_guard()
        if self.chaos is not None:
            # the request rides along so tenant-targeted client chaos
            # (slowloris@tenant=...) can pick its victim
            self.chaos.on_client(self._tick_no, req)
        if req.sink is not None:
            with self.tickprof.seg("sink", record=on_step) as sg:
                try:
                    req.sink(ev)
                except Exception:  # noqa: BLE001
                    # a client that died mid-stream must cost ITS
                    # request, never the engine: drop the sink, let the
                    # slot finish out its budget (eos/budget latch frees
                    # it) — and say so on the stream, a vanished consumer
                    # is evidence
                    req.sink = None
                    self.metrics.on_dropped_sink()
                    self.tracer.event("client_disconnected",
                                      request=req.id, tick=self._tick_no,
                                      **_tr(req))
            # charge transport time to the REQUEST (a slow client must
            # show up in its own tail attribution, not vanish into the
            # decode gap it inflates)
            dt = sg.gross
            req.client_write_s += dt
            if on_step:
                # serial with the decode-gap netting that reads it, and
                # both block live slots' gaps (a dead client stalling a
                # timeout write must not read as decode)
                self._sink_s += dt
            self.metrics.on_client_write(dt)
        if self.on_event is not None:
            self.on_event(ev)
        if ev.finished or ev.kind != "token":
            # stamped AFTER the sink write, the same clock edge
            # `_on_finished` uses for e2e: the final token's delivery is
            # part of the request's life, or a slow client's last write
            # would be charged to client_write yet fall outside e2e and
            # the phases could sum past the total — and every reporter
            # (request_finished event, loadgen e2e) reads this one stamp
            req.finished_at = _CLOCK()
            req.done.set()

    def _on_finished(self, req) -> None:
        """Terminal accounting for a completed request: SLO metrics,
        phase histograms, and the `request_finished` event whose
        per-phase totals are what `obs trace` decomposes tails with.
        e2e ends at `finished_at`, which `_emit` stamps after the final
        sink write — the single terminal clock edge every reporter
        (this event, the histograms, loadgen) agrees on."""
        now = req.finished_at if req.finished_at is not None \
            else _CLOCK()
        self.metrics.on_finish(req, now)
        reason = ("eos" if self.cfg.eos_id is not None and req.tokens
                  and req.tokens[-1] == self.cfg.eos_id else "budget")
        req.finish_reason = reason
        self.metrics.on_phases(req)
        self.tracer.event(
            "request_finished", request=req.id, tick=self._tick_no,
            reason=reason, prompt_len=req.prompt_len,
            n_tokens=len(req.tokens), preempts=req.preempts,
            e2e_s=round(now - req.submitted_at, 6),
            ttft_s=(round(req.first_token_at - req.submitted_at, 6)
                    if req.first_token_at is not None else None),
            **{f"{p}_s": round(v, 6) for p, v in req.phases_s().items()},
            **_tr(req),
        )

    # -------------------------------------------------------- public api

    def submit(self, req: Request) -> tuple[bool, str | None]:
        """Queue a request (thread-safe). Rejections emit immediately —
        backpressure the caller can act on, not a silent drop."""
        gov = self._governor
        gov_active = gov is not None and gov.active
        # shed order made admission policy: batch clamps whenever ANY
        # brownout holds (local governor or router-ordered); interactive
        # clamps only when the local governor is active AND the batch
        # queue is already empty — batch absorbs every degradation
        # first, and a router order alone never touches interactive
        clamp_this = (gov_active or self._class_brownout) \
            if req.sla_class == CLASS_BATCH \
            else (gov_active and self.queue.depth_of(CLASS_BATCH) == 0)
        if clamp_this and self.cfg.brownout_clamp > 0 \
                and req.max_new_tokens > self.cfg.brownout_clamp:
            # brownout clamp, applied BEFORE the journal sees the
            # request: the WAL must record the budget actually served,
            # or a replay would un-clamp it mid-overload
            req.clamped_from = req.max_new_tokens
            req.max_new_tokens = self.cfg.brownout_clamp
        if self.journal is not None:
            # write-AHEAD of queue.submit: the instant the request is
            # in the queue the engine thread may pop it and emit its
            # first token, and that token's journal record needs the
            # admit record already on disk. A door rejection below
            # closes the speculative record with a terminal one, so it
            # can never replay.
            self.journal.admit(req)
            req._journaled = True
            self._journal_guard()
        ok, reason = self.queue.submit(req)
        if ok:
            self.metrics.on_accept(req.sla_class)
            if req.clamped_from is not None:
                self.metrics.on_clamp(req.sla_class)
            self.tracer.event("request_admitted", request=req.id,
                              prompt_len=req.prompt_len,
                              max_new_tokens=req.max_new_tokens,
                              deadline_s=req.deadline_s,
                              sla_class=req.sla_class,
                              **({"tenant": req.tenant}
                                 if req.tenant else {}),
                              **({"clamped_from": req.clamped_from}
                                 if req.clamped_from is not None else {}),
                              **_tr(req))
        else:
            # queued_s: rejection happens at the door, so the request
            # spent zero time queued — the key exists so rejects land in
            # the same attribution tables as everything else
            req.finish_reason = "rejected"
            self.metrics.on_reject(reason)
            self.tracer.event("request_rejected", request=req.id,
                              reason=reason, prompt_len=req.prompt_len,
                              sla_class=req.sla_class,
                              **({"tenant": req.tenant}
                                 if req.tenant else {}),
                              queued_s=0.0, **_tr(req))
            self._emit(TokenEvent(req, None, True, kind="rejected",
                                  reason=reason))
        return ok, reason

    def reject_unparsed(self, rid: str | None, error: str) -> None:
        """Front-end hand-off for a line that never became a Request:
        counted and evented like a door reject so malformed input is
        visible in the same tables — and never an engine-thread
        exception, whatever the line contained."""
        self.metrics.on_reject(REJECT_BAD_REQUEST)
        self.tracer.event(
            "request_rejected",
            request=rid or f"unparsed_{next(self._unparsed)}",
            reason=REJECT_BAD_REQUEST, error=str(error)[:200],
            queued_s=0.0)

    # ------------------------------------------------- drain + recovery

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self, timeout_s: float = 30.0) -> None:
        """Flip to graceful drain (idempotent): the queue closes with
        `reject(reason="draining")`, in-flight slots — and requests
        already accepted into the queue — run to eos/budget, bounded by
        `timeout_s`. The SIGTERM/SIGINT path (serve/server.py) lands
        here."""
        if self._draining:
            return
        self._draining = True
        self._drain_deadline = _CLOCK() + max(0.0, timeout_s)
        self.queue.close(REJECT_DRAINING)
        self.tracer.event("serve_draining", tick=self._tick_no,
                          active=self.n_active, queue=len(self.queue),
                          timeout_s=timeout_s)
        self.hb.pulse(phase="drain", step=self._tick_no,
                      active=self.n_active, queue=len(self.queue))

    def drain_expired(self) -> bool:
        return (self._draining and self._drain_deadline is not None
                and _CLOCK() > self._drain_deadline)

    def replay_pending(self, sink=None, *,
                       max_replays: int = MAX_REPLAYS_DEFAULT) -> dict:
        """Recover the journal into this engine — called once, after
        `warmup`, before the serve loop. Unfinished journaled requests
        re-enter HEAD of queue (original admit order preserved) with
        their generated tokens riding along; the next pop re-prefills
        prompt + generated through the same recompute path preemption
        uses, so the continuation is bit-identical and `obs trace`
        shows it as a resumed request. Requests whose output was
        already complete just owe the client a terminal event; requests
        that crashed the engine `max_replays` times are quarantined
        with a `request_poisoned` event instead of crash-looping."""
        if self.journal is None:
            return {"resumed": 0, "finished": 0, "poisoned": 0,
                    "clean": True}
        resume, finished, poisoned, clean = self.journal.recover(
            max_replays=max_replays, eos_id=self.cfg.eos_id)
        self._journal_guard()
        for req in finished:
            req.sink = sink
            req.status = "done"
            req.finish_reason = "recovered_complete"
            self.tracer.event(
                "request_finished", request=req.id, tick=self._tick_no,
                reason="recovered_complete", prompt_len=req.prompt_len,
                n_tokens=len(req.tokens), preempts=req.preempts,
                replayed=True, **_tr(req))
            self._emit(TokenEvent(req, None, True, kind="done",
                                  reason="recovered_complete"))
        for req in poisoned:
            req.sink = sink
            req.status = "rejected"
            req.finish_reason = REJECT_POISONED
            self.metrics.on_poisoned()
            self.tracer.event(
                "request_poisoned", request=req.id, replays=req.replays,
                prompt_len=req.prompt_len, generated=len(req.tokens),
                **_tr(req))
            self._emit(TokenEvent(req, None, True, kind="rejected",
                                  reason=REJECT_POISONED))
        for req in reversed(resume):  # reversed: first-admitted at head
            req.sink = sink
            req._journaled = True
            if req.tokens:
                # resumed mid-decode: its next queue wait banks as
                # replay, its prefill as replay_prefill, and no second
                # first-token event fires — the PR-7 resume vocabulary
                req._preempted = True
                req.first_token_at = req.submitted_at
            self.metrics.on_replay()
            self.tracer.event(
                "request_admitted", request=req.id,
                prompt_len=req.prompt_len,
                max_new_tokens=req.max_new_tokens,
                deadline_s=req.deadline_s, replayed=True,
                replay_n=req.replays, generated=len(req.tokens),
                **_tr(req))
            self.queue.push_front(req)
        if resume or finished or poisoned:
            self.tracer.event("journal_replayed", resumed=len(resume),
                              finished=len(finished),
                              poisoned=len(poisoned))
        return {"resumed": len(resume), "finished": len(finished),
                "poisoned": len(poisoned), "clean": clean}

    @property
    def n_active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def idle(self) -> bool:
        return self.n_active == 0 and len(self.queue) == 0

    def _phase(self) -> str:
        if self._draining:
            return "drain"
        return "serve" if (self.n_active or len(self.queue)) \
            else "serve_idle"

    def _slo_tick(self, now: float | None = None) -> None:
        """Advance the SLO burn-rate state machines (rate-limited
        inside the monitor). Transitions emit the standard
        alert_raised/alert_cleared events AND an unconditional
        heartbeat pulse: the heartbeat's `alerts` field is how the
        router and `obs top` see a replica's alarm state without
        opening its stream."""
        if self.slo is None:
            return
        trs = self.slo.evaluate(now)
        if trs:
            slo_mod.publish(trs, self.tracer, self.metrics.reg,
                            step=self._tick_no,
                            active=len(self.slo.active))
            self.hb.pulse(step=self._tick_no, phase=self._phase(),
                          active=self.n_active, queue=len(self.queue),
                          alerts=self.slo.active_names())

    def exposition(self, window_s: float = DEFAULT_WINDOW_S) -> dict:
        """Live snapshot for the exposition socket (obs/export.py):
        current loop state + lifetime metrics + the last-`window_s`
        windowed roll-up. Host floats and bounded ring copies only —
        answering can never touch the device or trace a jit, whatever
        thread asks."""
        reg = self.metrics.reg
        gov = self._governor
        return {
            "role": "engine",
            "run": self.tracer.run,
            "phase": self._phase(),
            "tick": self._tick_no,
            "active": self.n_active,
            "slots": self.cfg.slots,
            "occupancy": round(self.n_active / self.cfg.slots, 4)
            if self.cfg.slots else 0.0,
            "queue": len(self.queue),
            "queue_by_class": self.queue.depth_by_class(),
            "draining": self._draining,
            "brownout": bool(gov.active) if gov is not None else False,
            # the act dict: what degradation/scheduling posture this
            # engine is in RIGHT NOW (obs top's `act` column)
            "act": {
                "class_brownout": self._class_brownout,
                "brownout": bool(gov.active) if gov is not None else False,
                "chunking": len(self._chunking),
            },
            "blocks_in_use": self.mgr.in_use,
            "blocks_free": self.mgr.num_free,
            "alerts": (self.slo.active_names()
                       if self.slo is not None else []),
            "metrics": reg.snapshot(),
            "windows": reg.windowed_snapshot(window_s),
            "memory": self.memory_ledger(),
            "tickprof": self.tickprof.snapshot(window_s),
            "compile": {**self.ledger.last_seen,
                        "recompiles": self.ledger.recompiles},
        }

    def memory_ledger(self) -> dict:
        """Live memory accounting from known shapes — param bytes, the
        KV pool's full and in-use footprint, host RSS. Pure host
        arithmetic (`.nbytes` is metadata, `_block_bytes` a cached
        int), so any thread may ask."""
        # by layer kind: a kind's block costs its own layers' bytes
        by_kind = {
            k: {"pool_bytes": int(m.num_blocks * self._kind_block_bytes[k]),
                "in_use_bytes": int(m.in_use * self._kind_block_bytes[k])}
            for k, m in self._mgrs.items()}
        # HBM the paged-read strategy copies per decode tick: the
        # gather path materializes every slot's chain (mapped or null;
        # of a windowed kind the slice of it a query can see) into a
        # contiguous view; the paged-attention kernel reads the pools
        # in place, so the copy is zero.
        gather = 0 if self._tick_read == "pallas" else int(self.cfg.slots * sum(
            self._view[k] * bb for k, bb in self._kind_block_bytes.items()))
        return {
            "param_bytes": self._param_bytes,
            "kv_pool_bytes": sum(v["pool_bytes"] for v in by_kind.values()),
            "blocks_in_use_bytes": sum(
                v["in_use_bytes"] for v in by_kind.values()),
            "kv_by_kind": by_kind,
            # what one position costs over every layer (and every step
            # of a looped model's cache)
            "kv_bytes_per_token": sum(
                self._kind_block_bytes.values()) // self.cfg.block_size,
            "kv_gather_bytes_per_tick": gather,
            # the host tier's occupancy rides the same ledger the HBM
            # numbers do — spilled KV is memory too, just cheaper
            "host_cache_mb": round(self.host.occupancy_mb, 3)
            if self.host is not None else 0.0,
            "host_cache_budget_mb": self.cfg.host_cache_mb,
            "rss_mb": hb_host_rss_mb(),
        }

    def _flight_payload(self) -> dict:
        """What a flight-record spill captures: loop state, the tick
        ring's tail, the windowed breakdown, compile counts, memory."""
        return {
            "phase": self._phase(),
            "active": self.n_active,
            "queue": len(self.queue),
            "ticks": self.tickprof.tail(32),
            "tickprof": self.tickprof.snapshot(),
            "compile": {**self.ledger.last_seen,
                        "recompiles": self.ledger.recompiles},
            "memory": self.memory_ledger(),
        }

    def flight_spill(self, reason: str, **extra) -> None:
        """Spill the flight record NOW — the server's SIGTERM handler,
        the fatal-exception path, and the final drain all call this.
        Host-only, so safe from a signal handler's frame."""
        if extra:
            self.flight.note(reason, **extra)
        self.flight.spill(reason, self._flight_payload(),
                          tick=self._tick_no)

    def control(self, req: dict) -> dict:
        """Control verbs arriving on the exposition socket (the
        request-line protocol in obs/export.py). `profile` brackets
        `jax.profiler.start_trace/stop_trace` on demand; anything
        unknown answers with an error dict instead of raising — the
        exporter thread must never die of a bad request."""
        cmd = req.get("cmd")
        if cmd == "profile":
            from hyperion_tpu.utils.profiling import on_demand_trace
            out = req.get("out")
            if not out:
                return {"status": "error", "error": "profile needs 'out'"}
            res = on_demand_trace(str(out),
                                  float(req.get("seconds") or 5.0))
            self.tracer.event("profile_requested", **res)
            return res
        if cmd == "class_brownout":
            # the router's degradation order (obs/export.py control
            # protocol): shed/clamp the batch class as if the local
            # governor were active, but never touch interactive — the
            # order means "yield batch capacity to the fleet", not
            # "this replica is drowning". Idempotent; a bool flip is
            # atomic under the GIL, so no lock against the engine
            # thread is needed.
            active = bool(req.get("active", True))
            changed = active != self._class_brownout
            self._class_brownout = active
            if changed:
                self.metrics.set_class_brownout(active)
                self.tracer.event("class_brownout", tick=self._tick_no,
                                  active=active, source="control")
            return {"status": "ok", "active": active, "changed": changed}
        return {"status": "error", "error": f"unknown cmd {cmd!r}"}

    def step(self) -> list[TokenEvent]:
        """One scheduling round: admit from the queue into free slots
        (block-gated, prefill, budget-limited), ensure every live slot
        owns its next write block (preempting on exhaustion), advance
        all active slots — one token each, or 1..spec_k+1 under the
        speculative tick — and route emissions. The whole round is one
        step of the host-tick profiler (obs/tickprof.py): the span
        `serve.step`, each stretch a `prof.seg(...)`. Journal and sink
        writes are segments of their own inside `_emit` wherever they
        happen, so the segments around them come out net of them."""
        # the record and the span carry the number the step STARTED under,
        # the one its `serve_tick` JSONL span and its events carry
        prof = self.tickprof
        with prof.tick(self._tick_no) as tk:
            emissions = self._step()
            # `count`: what the record's counters cost the step
            with prof.seg("count"):
                tk.count(
                    # positions whose keys and values the live slots hold in
                    # the pool: host bookkeeping, no device read
                    kv_tokens=sum(q.n_filled for q in self._seqs
                                  if q is not None),
                    # and those among them a windowed layer kind still holds
                    # (its leading blocks went back to the pool): per kind
                    **{f"kv_tokens_{k}": sum(
                        q.n_filled - self._allocs[k][s].first
                        * self.cfg.block_size
                        for s, q in enumerate(self._seqs) if q is not None)
                       for k, w in self._kinds.items() if w},
                    # an expert model's picks on this step's decode tick
                    **self._tick_counted,
                    # a looped model's decode tick: the steps it ran a row
                    # and the cache layers it wrote and read (steps x layers)
                    **self._loop_counted,
                    prefill_tokens=self._prefill_tokens,
                    # what the tick's rows asked of `sample_token_slots`,
                    # from the requests' own parameters: 0 and 0 = the tick
                    # ran the argmax alone, any restricted row = it sorted
                    # the vocabulary for every row
                    sampling_rows=self._sampling_rows,
                    restricted_rows=self._restricted_rows,
                    # how much of each kind's table the tick's read touched:
                    # the blocks the kernel's loops visit a layer (0 = the
                    # tick gathered), of the entries a gather copies a layer
                    **self._walk_counted,
                    # how the step's prefills, its chunk and its tick put
                    # keys and values into the pools, a layer of each kind:
                    # whole blocks, and positions row by row
                    **self._write_counted,
                    # an expert model: the (token, pick) rows the step's
                    # tick, chunk and prefills sent through each form of
                    # the grouped products, over the expert layers
                    **self._expert_rows,
                    # the positions of the step's prefills and chunk by
                    # the read their windows took: the tiled kernel over
                    # the gathered chain, or the gather's one softmax
                    **self._prompt_positions)
        if self.flight.due(self._tick_no):
            self.flight.spill("periodic", self._flight_payload(),
                              tick=self._tick_no)
        return emissions

    def _step(self) -> list[TokenEvent]:
        emissions: list[TokenEvent] = []
        now = _CLOCK()
        prof = self.tickprof
        self._prefill_tokens = 0
        self._sampling_rows = self._restricted_rows = 0
        self._walk_counted = self._no_walk
        self._write_counted = dict(self._no_write)
        self._expert_rows = dict(self._no_expert_rows)
        self._prompt_positions = dict.fromkeys(PROMPT_READ_COUNTERS, 0)
        self._tick_counted = {}
        self._loop_counted = {}

        if self._governor is not None:
            tr = self._governor.update(len(self.queue))
            if tr == "enter":
                self.metrics.set_brownout(True)
                self.tracer.event(
                    "brownout_enter", tick=self._tick_no,
                    depth=len(self.queue),
                    wait_p95_ms=round(self._governor.wait_p95() * 1e3, 3))
            elif tr == "exit":
                self.metrics.set_brownout(False)
                self.tracer.event("brownout_exit", tick=self._tick_no,
                                  depth=len(self.queue))
        gov_active = self._governor is not None and self._governor.active
        if gov_active or self._class_brownout:
            # shed deadline-aware, cheapest first, BATCH FIRST: queued
            # requests that cannot meet their deadline even if service
            # began after their CLASS's estimated wait are already
            # doomed — reject them NOW so the client retries elsewhere
            # instead of burning a queue slot toward a timeout.
            # Interactive is swept only when the local governor is
            # active AND batch is already empty (a router-ordered
            # class brownout alone never touches interactive).
            shed_classes = [CLASS_BATCH]
            if gov_active and self.queue.depth_of(CLASS_BATCH) == 0:
                shed_classes = [CLASS_INTERACTIVE]
            est = {cls: self._governor.wait_p95(cls)
                   for cls in shed_classes} \
                if self._governor is not None else {}
            for req in self.queue.shed_doomed(
                    now, est_wait_by_class=est,
                    classes=tuple(shed_classes)):
                self.metrics.on_shed(req.sla_class)
                req.finish_reason = REJECT_SHED
                # the standard reject vocabulary (shed=true rides
                # along): `obs trace` keeps shed requests in the
                # same attribution tables as door rejects, with
                # the queue time they DID burn before dying
                self.tracer.event(
                    "request_rejected", request=req.id,
                    tick=self._tick_no, reason=REJECT_SHED, shed=True,
                    sla_class=req.sla_class,
                    **({"tenant": req.tenant} if req.tenant else {}),
                    queued_s=round(max(0.0, now - req.enqueued_at), 6),
                    deadline_s=req.deadline_s)
                ev = TokenEvent(req, None, True, kind="rejected",
                                reason=REJECT_SHED)
                self._emit(ev)
                emissions.append(ev)

        with prof.seg("queue_pop"):
            free = [s for s, r in enumerate(self._slots) if r is None]
            if free:
                admit, expired = self.queue.pop_ready(
                    len(free), now, can_admit=self._can_admit)
                # pop_ready only expires requests it reaches; a block-gated
                # head stops the walk, so sweep the remainder too — a
                # deadline behind a stalled head must still fire on time
                expired += self.queue.drop_expired(now)
            else:
                admit, expired = [], self.queue.drop_expired(now)
            if CLASS_INTERACTIVE in self.queue.gate_blocked:
                # an interactive head is denied by the block gate while
                # batch work holds slots: preempt the YOUNGEST batch slot
                # to the queue (recompute resume — nothing is lost) so the
                # freed blocks admit the interactive head next round. One
                # victim per step: pool accounting settles between rounds,
                # and a single long prompt must not massacre the whole
                # batch tier in one tick.
                batch_live = [
                    s for s, r in enumerate(self._slots)
                    if r is not None and r.sla_class == CLASS_BATCH]
                if batch_live:
                    victim = max(batch_live,
                                 key=lambda t: self._seqs[t].order)
                    self._preempt(victim, reason="interactive_gate")
        # admit covers expiry + admission + their prefill calls, net of
        # journal/sink writes those paths perform
        with prof.seg("admit"):
            for req in expired:
                self.metrics.on_timeout()
                req.finish_reason = "timed_out"
                # enqueued_at, not submitted_at: a preempted-then-requeued
                # request that expires spent part of its life in a slot,
                # and that time is replay cost, not queue residency
                queued = round(max(0.0, now - req.enqueued_at), 6)
                self.tracer.event("request_timeout", request=req.id,
                                  waited_s=round(now - req.submitted_at, 3),
                                  queued_s=queued, **_tr(req))
                ev = TokenEvent(req, None, True, kind="timed_out",
                                reason="deadline exceeded in queue")
                self._emit(ev)
                emissions.append(ev)
            while admit:
                req = admit.pop(0)
                slot = free.pop(0)
                with prof.seg("admit/gate"):
                    if self.chaos is not None:
                        # poison_request@id=... fires here, at the moment
                        # the request is about to occupy a slot — the
                        # journal has its admit record, so the crash-
                        # replay counter (the poison-pill rule) sees
                        # every death it causes
                        self.chaos.on_request(req.id)
                    resumed = self._account_pop(req)
                ev = self._admit(req, slot)
                if ev is _CHUNK_ADMIT:
                    # the slot is claimed and prefilling in chunks across
                    # later steps; no token yet, nothing to emit
                    continue
                if ev is None:
                    # allocation raced an eviction between gate and admit:
                    # requeue head-first in arrival order and retry next
                    # round — degraded, never dropped. EVERY popped request
                    # streams the scheduled/requeued pair so no queue stint
                    # vanishes from the trace: the scheduled event banks
                    # the wait that just ended, the requeue mark starts the
                    # renewed one (and keeps resume flags for the re-pop)
                    req._preempted = resumed
                    for r in reversed([req] + admit):
                        if r.admitted_at is not None and r is not req:
                            r._preempted = self._account_pop(r)
                        self.tracer.event(
                            "request_requeued", request=r.id,
                            tick=self._tick_no, reason="alloc_race")
                        self._release_pending(r.id)
                        self.queue.push_front(r)
                    break
                self._emit(ev)
                emissions.append(ev)
                if ev.finished:
                    self._on_finished(req)

        # one chunked-prefill segment per step, interleaved with the
        # decode tick below — the whole point: co-running slots tick
        # every step while a long prompt fills in bounded bites
        with prof.seg("chunk"):
            for ev in self._advance_chunks():
                self._emit(ev)
                emissions.append(ev)
                if ev.finished:
                    self._on_finished(ev.request)

        if self.n_active:
            with prof.seg("ensure"):
                self._ensure_blocks()
        n_live = self.n_active - len(self._chunking)
        if n_live > 0:
            if self.chaos is not None:
                self.chaos.on_tick(self._tick_no)
            spec = self._spec
            cnts = accs = None
            # what the tick record's counters cost the step: `count`
            with prof.seg("count"):
                for s, req in enumerate(self._slots):
                    if req is not None and req.temperature > 0 \
                            and s not in self._chunking:
                        self._sampling_rows += 1
                        self._restricted_rows += \
                            req.top_k > 0 or req.top_p < 1.0
                self._walk_counted = self._count_walk()
                self._loop_counted = self._loop_per_tick
                # the tick writes each live slot's window row by row
                for _, positions in self._write_names.values():
                    self._write_counted[positions] += \
                        n_live * self._tick_width
                # the tick runs every slot's row through the experts,
                # live or masked out
                self._count_experts(self.cfg.slots * self._tick_width)
            with prof.seg("draft"):
                drafts = self._collect_drafts() if spec else None
            # the device call's wall splits into the host->device table
            # upload (`bt_upload`, when the table went stale) and
            # `device`: its children dispatch and fetch
            with self.tracer.span("serve_tick", step=self._tick_no) as sp:
                with prof.seg("device"):
                    if spec:
                        toks, cnts, accs, fins = \
                            self._spec_tick_device(drafts)
                    else:
                        toks, fins = self._tick_device()
                sp.set(active=self.n_active)
            emitted = 0
            slot_ticks = 0
            # accept host path: token routing + gap netting, net of the
            # journal/sink writes _emit times as segments of their own
            with prof.seg("accept"):
                tnow = _CLOCK()
                for s, req in enumerate(self._slots):
                    if req is None or s in self._chunking:
                        # a chunking slot is masked out of the tick — its
                        # lane computed pad into the null block, nothing
                        # to route
                        continue
                    slot_ticks += 1
                    n = int(cnts[s]) if spec else 1
                    if spec:
                        self.metrics.on_spec(self.cfg.spec_k, int(accs[s]))
                    if n == 0:
                        continue
                    self._seqs[s].n_filled += n
                    if self._windowed:
                        # what fell behind every window is free again
                        # within the step
                        self._slide_windows(s)
                    gap_from = getattr(req, "_last_emit_at", None)
                    if gap_from is not None:
                        # the gap is wall time shared by every slot: net it
                        # of ALL sink writes since this request's previous
                        # emission (its own are charged to client_write;
                        # neighbours' must not masquerade as decode). One
                        # verify pass produced n tokens, so TPOT charges
                        # the pass pro-rata across them — the per-token
                        # cadence a streaming client actually experiences
                        for _ in range(n):
                            self.metrics.on_token_gap((tnow - gap_from) / n,
                                                      req.sla_class)
                        sink = self._sink_s - getattr(
                            req, "_sink_mark", self._sink_s)
                        req.decode_s += max(0.0, tnow - gap_from - sink)
                    req._last_emit_at = tnow
                    req._sink_mark = self._sink_s
                    fin_slot = bool(fins[s])
                    # every accepted token flows through the SAME per-token
                    # path the sequential tick uses: one journal `tok`
                    # record, one stream index, one sink write apiece —
                    # failover dedup and replay never see speculation
                    for j in range(n):
                        tok = int(toks[s, j]) if spec else int(toks[s])
                        ev = TokenEvent(req, tok, fin_slot and j == n - 1)
                        self._emit(ev)
                        emissions.append(ev)
                        emitted += 1
                    if fin_slot:
                        self._on_finished(req)
                        self._free_slot(s)
                # the tick's own account closes inside its segment
                self.metrics.on_tick(emitted, slot_ticks)
                self._tick_no += 1
                if self.cfg.snapshot_every \
                        and self._tick_no % self.cfg.snapshot_every == 0:
                    with prof.seg("accept/snapshot"):
                        rss = hb_host_rss_mb()
                        if rss is not None:
                            # a gauge SERIES across snapshots — doctor
                            # reads the trend for its host-leak warning
                            self.metrics.reg.gauge("host_rss_mb").set(rss)
                        self.tracer.snapshot(
                            self.metrics.reg, step=self._tick_no,
                            tickprof=self.tickprof.snapshot())

        # the step's watch duties: the compile ledger, the gauges, the
        # SLO engine, the heartbeat
        with prof.seg("slo"):
            # compile ledger: 4 host-int reads per step. Any growth after
            # warmup is a broken invariant — count it, name the
            # executable, and leave churn context (what shape work just
            # ran) for doctor
            growth = self.ledger.check(self.compile_stats())
            if growth:
                self.metrics.on_recompile(
                    sum(g["after"] - g["before"] for g in growth))
                for g in growth:
                    ctx = dict(tick=self._tick_no, active=self.n_active,
                               queue=len(self.queue),
                               last_prefill_bucket=self._last_prefill_bucket)
                    self.tracer.event("recompile_after_warmup",
                                      executable=g["executable"],
                                      before=g["before"], after=g["after"],
                                      **ctx)
                    self.flight.note("recompile_after_warmup",
                                     executable=g["executable"], **ctx)
            self.metrics.observe_state(
                len(self.queue), self.n_active, self.cfg.slots)
            self.metrics.observe_cache(
                self.mgr.in_use, self.n_active, self._block_bytes)
            self._slo_tick()
            roots = self._hot_roots.top()
            self.hb.beat(step=self._tick_no, phase="serve",
                         active=self.n_active, queue=len(self.queue),
                         **({"alerts": self.slo.active_names()}
                            if self.slo is not None else {}),
                         **({"prefix_roots": roots} if roots else {}))
        return emissions

    def run(
        self,
        *,
        should_stop: Callable[[], bool] | None = None,
        drain_when: Callable[[], bool] | None = None,
        idle_sleep_s: float = 0.01,
    ) -> dict:
        """Serve until `should_stop()` (hard stop) or until
        `drain_when()` and the engine is idle (graceful drain; default:
        drain immediately once idle). Emits `serve_start`/`serve_end`
        lifecycle events — `obs doctor` reads `serve_end` as the
        terminal record separating a drained server from a hung one."""
        drain_when = drain_when or (lambda: True)
        self.tracer.event(
            "serve_start", slots=self.cfg.slots, max_len=self.cfg.max_len,
            block_size=self.cfg.block_size, num_blocks=self.cfg.num_blocks,
            prefix_cache=self.cfg.prefix_cache,
            host_cache_mb=self.cfg.host_cache_mb)
        self.hb.pulse(phase="serve", step=self._tick_no)
        try:
            while True:
                if should_stop is not None and should_stop():
                    break
                if self.drain_expired():
                    # the grace window closed with work still in hand:
                    # stop NOW — everything unfinished is journaled, so
                    # the next life replays it instead of losing it
                    self.tracer.event("drain_timeout", tick=self._tick_no,
                                      active=self.n_active,
                                      queue=len(self.queue))
                    break
                if self.idle:
                    # drain_when first, idle RE-checked after: a
                    # transport's last submit happens-before its EOF
                    # flag, so this ordering can never strand a request
                    # that raced the drain signal
                    if (self._draining or drain_when()) and self.idle:
                        break
                    # idle SLO ticks: an alert raised under load must
                    # be able to CLEAR while the loop sits idle after
                    # the load drops — step() is not running, so the
                    # idle loop owns the evaluation cadence here
                    self._slo_tick()
                    # same payload shape as the serve beat so a watcher
                    # (obs doctor) reads occupancy whichever phase the
                    # loop froze in
                    idle_roots = self._hot_roots.top()
                    self.hb.beat(step=self._tick_no, phase="serve_idle",
                                 active=0, queue=len(self.queue),
                                 **({"alerts": self.slo.active_names()}
                                    if self.slo is not None else {}),
                                 **({"prefix_roots": idle_roots}
                                    if idle_roots else {}))
                    time.sleep(idle_sleep_s)
                    continue
                self.step()
        except BaseException as e:
            # the flight record IS the post-mortem: spill before the
            # exception unwinds the process so doctor can cite the
            # final ticks even when nothing catches it upstream
            self.flight_spill("fatal_exception", error=repr(e)[:200])
            raise
        finally:
            summary = self.metrics.summary()
            self.tracer.snapshot(self.metrics.reg, step=self._tick_no)
            self.tracer.event(
                "serve_end", ticks=self._tick_no,
                completed=summary["completed"],
                rejected=summary["rejected"],
                timed_out=summary["timed_out"],
                tokens=summary["tokens"],
                prefix_hits=summary["prefix_hits"],
                preempted=summary["preempted"],
                alerts_raised=summary["alerts_raised"],
                # the tier split rides the terminal record so smoke/
                # doctor read host-tier evidence without a snapshot
                tier_hits_host=summary["tier_hits_host"],
                tier_hits_device=summary["tier_hits_device"],
                tier_miss=summary["tier_miss"],
                host_spilled_blocks=summary["host_spilled_blocks"],
                host_restored_blocks=summary["host_restored_blocks"],
            )
            if self.host is not None and self.cfg.host_cache_dir:
                try:
                    st = self.host.save(self.cfg.host_cache_dir)
                    self.tracer.event(
                        "hostcache_saved", chains=st["chains"],
                        mb=st["mb"], path=self.cfg.host_cache_dir)
                except OSError as e:
                    # persistence is an optimization, never a crash on
                    # the drain path — say so and finish the drain
                    print(f"[serve] host-cache save failed: {e}",
                          file=sys.stderr)
            self.flight_spill("serve_end")
            # the file holds only the LAST beat, so the terminal pulse
            # repeats the occupancy payload — a watcher reading a
            # "done" heartbeat still sees what the loop drained to
            self.hb.close(phase="done", tokens=summary["tokens"],
                          active=self.n_active, queue=len(self.queue))
        return summary
