"""Serving layer: continuous-batching oracle vs `infer.generate`,
recompile-free slot churn, admission-queue policy, per-slot sampling,
the JSONL transports, serve telemetry through obs, and the chaos seam.

The two acceptance anchors from the issue live here in tier-1:

  * **Oracle** — a temp-0 request decoded through the engine while
    other slots churn produces bit-identical tokens to
    `infer/generate.generate` on the same prompt.
  * **No recompile** — after `warmup`, arbitrary admission/refill/
    decode never adds an executable to either jit cache.
"""

from __future__ import annotations

import gc
import io
import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.infer.generate import (
    generate,
    sample_token,
    sample_token_slots,
    sampling_tier,
)
from hyperion_tpu.models.llama import Llama, init_cache, llama_tiny_config
from hyperion_tpu.serve.engine import Engine, EngineConfig
from hyperion_tpu.serve.loadgen import LoadSpec, run_load
from hyperion_tpu.serve.metrics import ServeMetrics
from hyperion_tpu.serve.queue import (
    REJECT_QUEUE_FULL,
    REJECT_TOO_LONG,
    AdmissionQueue,
    Request,
)


@pytest.fixture(scope="module")
def llama():
    model = Llama(llama_tiny_config(max_len=64))
    params = model.init_params(jax.random.key(0), seq=8)
    return model, {"params": params}


def _prompts(ns, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in ns]


def _engine(llama, **kw):
    model, variables = llama
    cfg = dict(slots=3, max_len=48, eos_id=None)
    cfg.update(kw)
    return Engine(model, variables, EngineConfig(**cfg))


def _drain(engine, max_steps=500):
    steps = 0
    while not engine.idle:
        engine.step()
        steps += 1
        assert steps < max_steps, "engine failed to drain"


# ------------------------------------------------------------- oracle


class TestOracle:
    def test_temp0_bit_identical_with_slot_churn(self, llama):
        """The acceptance oracle: every request decoded through the
        engine — slots refilling around it the whole time — emits
        exactly the tokens `generate` emits for its prompt."""
        model, variables = llama
        eng = _engine(llama)
        eng.warmup([8, 16])
        prompts = _prompts([5, 9, 4, 12, 7, 6, 10, 3])
        reqs = [
            Request(prompt_ids=p, max_new_tokens=4 + (i * 3) % 9,
                    id=f"r{i}")
            for i, p in enumerate(prompts)
        ]
        for r in reqs:  # 8 requests through 3 slots: constant churn
            ok, reason = eng.submit(r)
            assert ok, reason
        _drain(eng)
        for r in reqs:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens,
            ))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
            assert r.status == "done"

    def test_eos_stops_request(self, llama):
        """eos semantics mirror `generate`: the eos token is delivered,
        then the request finishes (generate pads; the engine frees the
        slot)."""
        model, variables = llama
        probe = _prompts([6], seed=3)[0]
        ref = np.asarray(generate(
            model, variables, jnp.asarray(probe)[None], 10))[0]
        eos = int(ref[2])  # force eos at the 3rd emitted token
        eng = _engine(llama, eos_id=eos)
        eng.warmup([8])
        req = Request(prompt_ids=probe, max_new_tokens=10)
        eng.submit(req)
        _drain(eng)
        ref_eos = np.asarray(generate(
            model, variables, jnp.asarray(probe)[None], 10,
            eos_id=eos, pad_id=0,
        ))[0]
        cut = int(np.argmax(ref_eos == eos)) + 1
        assert req.tokens == ref_eos[:cut].tolist()
        assert req.tokens[-1] == eos
        assert eng.n_active == 0

    def test_vector_cache_index_matches_scalar(self, llama):
        """Model-level pin for the per-slot decode path: a batch where
        every row sits at the SAME depth must produce identical logits
        through the vector-cache_index path and the scalar one."""
        model, variables = llama
        B, P = 2, 6
        ids = jnp.asarray(_prompts([P], seed=5)[0])[None].repeat(B, 0)
        cache = init_cache(model.cfg, B, max_len=16)
        _, cache = model.apply(variables, ids, cache=cache, cache_index=0)
        tok = ids[:, -1:]
        scalar_logits, _ = model.apply(
            variables, tok, cache=cache, cache_index=jnp.int32(P))
        vector_logits, _ = model.apply(
            variables, tok, cache=cache,
            cache_index=jnp.full((B,), P, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(scalar_logits), np.asarray(vector_logits))


# ------------------------------------------------- recompile guarantee


class TestNoRecompile:
    def test_slot_churn_never_recompiles(self, llama):
        """After warmup, admission/refill/decode with varying sampling
        params, prompt lengths (within warmed buckets), and occupancy
        must not add a single executable to either jit cache."""
        eng = _engine(llama)
        stats0 = eng.warmup([4, 8, 16])
        rng = np.random.default_rng(7)
        for i in range(12):
            eng.submit(Request(
                prompt_ids=rng.integers(1, 250, int(rng.integers(3, 16))),
                max_new_tokens=int(rng.integers(1, 8)),
                temperature=float(rng.choice([0.0, 0.7, 1.3])),
                top_k=int(rng.choice([0, 5, 20])),
                top_p=float(rng.choice([1.0, 0.9])),
                seed=i,
            ))
            eng.step()
        _drain(eng)
        assert eng.compile_stats() == stats0, (
            "slot churn recompiled the engine")

    def test_warmup_compiles_one_tick_and_one_prefill_per_bucket(
            self, llama):
        # the ladder covers every bucket UP TO the largest requested
        # length ({8, 16, 24} at max_len 24), because a prefix hit
        # shrinks a prompt into any smaller bucket and must never cost
        # a compile. The jit caches are process-wide (`_shared_jits`),
        # so the assertion is on the DELTA warmup adds for this
        # engine's unique shapes.
        eng = _engine(llama, max_len=24)
        before = eng.compile_stats()
        stats = eng.warmup([4, 8, 16, 23])
        assert stats["tick_executables"] - before["tick_executables"] == 1
        assert stats["prefill_executables"] \
            - before["prefill_executables"] == 3
        assert stats["copy_executables"] >= 1  # the COW block copy

    def test_optimistic_warmup_extends_ladder_to_max_len(self, llama):
        # preemption-resumes grow prompts (prompt + generated), so
        # optimistic admission warms the whole ladder — {8, 16} at
        # max_len 16 — even though only 8 was requested
        eng = _engine(llama, admission="optimistic", max_len=16, slots=5)
        before = eng.compile_stats()
        stats = eng.warmup([8])
        assert stats["prefill_executables"] \
            - before["prefill_executables"] == 2


# ----------------------------------------- paged-attention kernel


class TestPagedAttnPallas:
    """PR-19 acceptance: the engine with `paged_attn_impl="pallas"`
    (the in-kernel block-table walk, interpret mode on CPU) streams
    bit-identical tokens to `generate` under slot churn with
    prefix-shared (COW) prompts, and stays recompile-free — the knob
    lives in the model config, so every shared jit keeps its signature
    and table contents stay runtime data. One warmed bucket and short
    decodes keep it inside the tier-1 wall guard."""

    def test_pallas_streams_match_generate_with_flat_compiles(self, llama):
        import dataclasses

        model, variables = llama
        pmodel = Llama(dataclasses.replace(
            model.cfg, paged_attn_impl="pallas"))
        eng = Engine(pmodel, variables,
                     EngineConfig(slots=2, max_len=32, eos_id=None,
                                  block_size=8))
        stats0 = eng.warmup([8])
        # shared 5-token prefix: rows radix-share blocks, then COW on
        # divergence — the kernel must read shared chains correctly
        rng = np.random.default_rng(11)
        head = rng.integers(1, 250, 5)
        reqs = [
            Request(prompt_ids=np.concatenate(
                [head, rng.integers(1, 250, 1 + i % 3)]).astype(np.int32),
                max_new_tokens=3 + i % 3, id=f"p{i}")
            for i in range(4)
        ]
        for r in reqs:  # 4 requests through 2 slots: churn
            ok, reason = eng.submit(r)
            assert ok, reason
        _drain(eng)
        assert eng.compile_stats() == stats0, (
            "pallas paged attention recompiled the engine")
        for r in reqs:
            # reference decodes on the GATHER slab path: temp-0 argmax
            # absorbs the kernel's ~1e-7 online-softmax delta, so the
            # user-visible streams are bit-identical
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens,
            ))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
            assert r.status == "done"
        # the ledger shows the win: no per-tick gather copy
        assert eng.memory_ledger()["kv_gather_bytes_per_tick"] == 0

    def test_gather_ledger_reports_copy_bytes(self, llama):
        eng = _engine(llama, slots=2, max_len=32, block_size=8)
        led = eng.memory_ledger()
        # slots x blocks-per-table x block bytes, and strictly positive:
        # the default `auto` resolves to the gather off a TPU
        assert llama[0].cfg.paged_attn_impl == "auto"
        assert led["kv_gather_bytes_per_tick"] == \
            2 * eng._mb * eng._block_bytes > 0

    # every window the benchmark's serving cells dispatch, by the query
    # heads a KV head of the cell's model: the tick, Mistral's verify
    # window, each prefill bucket (a final piece's too) and the chunk
    CELL_WINDOWS = {
        "mistral7b": (4, {
            1: "pallas", 4: "pallas", 8: "gather", 16: "gather",
            32: "gather", 64: "gather", 128: "gather", 256: "gather",
            512: "tiled", 1024: "tiled", 2048: "tiled"}),
        "trinity": (6, {
            1: "pallas", 8: "gather", 16: "gather", 32: "gather",
            64: "gather", 128: "gather", 256: "gather", 512: "tiled"}),
        "smallthinker": (7, {
            1: "pallas", 8: "gather", 16: "gather", 32: "gather",
            64: "gather", 128: "gather", 256: "gather", 512: "tiled"}),
        # one query row a KV head: the two narrowest buckets are no
        # wider than a verify window and read in place, as they did;
        # no bucket reaches the tiled kernel's rows
        "ouro": (1, {
            1: "pallas", 8: "pallas", 16: "pallas", 32: "gather",
            64: "gather", 128: "gather", 256: "gather", 512: "gather"}),
    }

    @pytest.mark.parametrize("backend", ["tpu", "cpu"])
    @pytest.mark.parametrize("cell, window", [
        (cell, w) for cell, (_, by_window) in CELL_WINDOWS.items()
        for w in by_window])
    def test_auto_selects_from_shape_and_backend(self, cell, window,
                                                 backend):
        from hyperion_tpu.models.llama import (
            PAGED_KERNEL_MAX_ROWS,
            PAGED_TILED_MIN_ROWS,
            select_paged_attn_impl,
        )

        rep, by_window = self.CELL_WINDOWS[cell]
        # the interpreter is no read path: off a TPU everything gathers
        want = by_window[window] if backend == "tpu" else "gather"
        assert select_paged_attn_impl(window, rep, backend) == want
        on_tpu = select_paged_attn_impl(window, rep, "tpu")
        assert (window * rep <= PAGED_KERNEL_MAX_ROWS) == \
            (on_tpu == "pallas")
        assert (window * rep >= PAGED_TILED_MIN_ROWS) == \
            (on_tpu == "tiled")

    def test_auto_lowers_the_gather_programs_on_cpu(self, llama):
        """Off a TPU `auto` is the gather: the tick and the prefill the
        engine lowers are the programs `gather` lowers, text for text
        (tier-1's programs and their time are what they were)."""
        import dataclasses

        model, variables = llama
        texts = {}
        for impl in ("auto", "gather"):
            m = Llama(dataclasses.replace(model.cfg, paged_attn_impl=impl))
            eng = Engine(m, variables, EngineConfig(
                slots=2, max_len=32, eos_id=None, block_size=8))
            assert eng._tick_read == "gather"
            tick = eng._tick_jit.lower(
                m, None, eng.cfg.pad_id, variables, eng._cache,
                eng._state, *eng._tables_on_device()).as_text()
            prefill = eng._prefill_jit.lower(
                m, None, variables, eng._cache, eng._state,
                jnp.zeros((1, 8), jnp.int32), eng._rows_on_device(0),
                jnp.int32(0), jnp.int32(0), jnp.int32(5),
                jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
                jnp.int32(4), jax.random.key(0)).as_text()
            texts[impl] = (tick, prefill)
        assert texts["auto"] == texts["gather"]
        assert "pallas" not in texts["auto"][0]

    def test_tick_record_counts_the_blocks_walked(self, llama, tmp_path):
        """`kv_blocks_walked`: what the kernel's loops visit in the
        step's decode tick, a layer, from the slots' own lengths (a
        live slot's chain up to the position it writes, one null block
        of a masked lane), beside the entries a gather copies; 0 when
        the tick gathered. `obs doctor` says it in words."""
        import dataclasses

        from hyperion_tpu.obs import doctor

        model, variables = llama
        pmodel = Llama(dataclasses.replace(
            model.cfg, paged_attn_impl="pallas"))
        bs = 8
        eng = Engine(pmodel, variables,
                     EngineConfig(slots=3, max_len=32, eos_id=None,
                                  block_size=bs))
        eng.warmup([8])
        for i, n in enumerate((5, 9)):      # two of three slots live
            eng.submit(Request(
                prompt_ids=np.arange(1, n + 1, dtype=np.int32),
                max_new_tokens=4 + 6 * i, id=f"w{i}"))
        ticks = 0
        while not eng.idle:
            eng.step()
            rec = eng.tickprof.tail(1)[0]
            c = rec["c"]
            if "device" not in rec["s"]:
                assert c["kv_blocks_walked"] == c["kv_table_entries"] == 0
                continue
            ticks += 1
            assert c["kv_table_entries"] == 3 * eng._mb
            # after the tick a slot it advanced holds one position
            # more: the one the walk ended on. A request that finished
            # in this step has left its slot by now: count by tokens
            held = [len(r.prompt_ids) + len(r.tokens) - 1
                    for r in eng._slots if r is not None]
            walked_live = sum(-(-n // bs) for n in held)
            assert c["kv_blocks_walked"] >= walked_live + (3 - len(held))
            assert c["kv_blocks_walked"] <= walked_live + 3 * (32 // bs)
        assert ticks >= 9
        snap = eng.tickprof.snapshot()
        n, m = (snap["counters"]["kv_blocks_walked"],
                snap["counters"]["kv_table_entries"])
        assert 0 < n < m == ticks * 3 * eng._mb
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        assert f"read in place: {n} of {m} table entries" in row
        # the gather path walks nothing and says so
        geng = _engine(llama, slots=2, max_len=32, block_size=bs)
        geng.warmup([8])
        geng.submit(Request(prompt_ids=np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=3, id="g"))
        _drain(geng)
        gc = geng.tickprof.snapshot()["counters"]
        assert gc["kv_blocks_walked"] == 0 < gc["kv_table_entries"]


# ------------------------------------------------- paged KV cache


class TestPagedCache:
    """The PR-6 tentpole: block-granular KV memory + radix prefix
    reuse (serve/blocks.py) behind the same engine contract — bit-
    identical tokens, zero post-warmup recompiles."""

    def test_paged_model_path_matches_contiguous(self, llama):
        """Model-level pin: the block-table gather path produces
        bit-identical logits to the contiguous cache, for both the
        scalar (prefill) and vector (tick) cache_index forms."""
        from hyperion_tpu.models.llama import init_cache, init_paged_cache

        model, variables = llama
        B, P, bs = 2, 9, 8
        ids = jnp.asarray(_prompts([P], seed=21)[0])[None].repeat(B, 0)
        cache = init_cache(model.cfg, B, max_len=32)
        ref0, cache = model.apply(variables, ids, cache=cache, cache_index=0)
        tok = ids[:, -1:]
        ref1, _ = model.apply(
            variables, tok, cache=cache,
            cache_index=jnp.full((B,), P, jnp.int32))

        pool = init_paged_cache(model.cfg, 1 + 2 * 4, bs)
        bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        pg0, pool = model.apply(variables, ids, cache=pool, cache_index=0,
                                block_tables=bt)
        pg1, _ = model.apply(
            variables, tok, cache=pool,
            cache_index=jnp.full((B,), P, jnp.int32), block_tables=bt)
        np.testing.assert_array_equal(np.asarray(ref0), np.asarray(pg0))
        np.testing.assert_array_equal(np.asarray(ref1), np.asarray(pg1))

    def test_prefix_hit_skips_prefill_and_stays_bit_identical(self, llama):
        """The headline behavior: requests sharing a system prompt
        reuse its blocks (hit rate + tokens saved > 0) and still emit
        exactly what `generate` emits for their full prompt."""
        model, variables = llama
        eng = _engine(llama, block_size=8)
        stats0 = eng.warmup([22])
        rng = np.random.default_rng(31)
        shared = rng.integers(1, 250, 16).astype(np.int32)
        reqs = [
            Request(prompt_ids=np.concatenate(
                [shared, rng.integers(1, 250, 3 + i).astype(np.int32)]),
                max_new_tokens=4, id=f"sp{i}")
            for i in range(3)
        ]
        for r in reqs:
            ok, reason = eng.submit(r)
            assert ok, reason
        _drain(eng)
        for r in reqs:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
        s = eng.metrics.summary()
        assert s["prefix_hits"] >= 2
        assert s["prefill_tokens_saved"] >= 2 * 16
        assert s["prefix_hit_rate"] > 0
        assert eng.compile_stats() == stats0

    def test_mid_block_divergence_cow_forks_not_aliases(self, llama):
        """A prompt diverging mid-block COW-copies the shared block:
        one copy counted, the original requester's blocks untouched
        (its own continuation stays bit-identical), the fork's output
        bit-identical to its full prompt."""
        model, variables = llama
        eng = _engine(llama, block_size=8)
        stats0 = eng.warmup([26])
        rng = np.random.default_rng(33)
        A = rng.integers(1, 250, 24).astype(np.int32)
        B = np.concatenate([A[:20], rng.integers(1, 250, 6).astype(np.int32)])
        ra = Request(prompt_ids=A, max_new_tokens=4, id="cowA")
        eng.submit(ra)
        _drain(eng)
        rb = Request(prompt_ids=B, max_new_tokens=4, id="cowB")
        ra2 = Request(prompt_ids=A, max_new_tokens=6, id="cowA2")
        eng.submit(rb)
        eng.submit(ra2)
        _drain(eng)
        for r in (ra, rb, ra2):
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
        assert eng.metrics.summary()["cow_copies"] >= 1
        assert eng.compile_stats() == stats0

    def test_churn_with_hits_cow_and_preemption_never_recompiles(
            self, llama):
        """The acceptance churn: 12 requests through an optimistically
        admitted, deliberately undersized pool — prefix hits, COW
        forks, and pool-exhaustion preemptions all occur, every output
        stays bit-identical to `generate`, the jit caches stay flat,
        and the pool accounts to zero at drain."""
        model, variables = llama
        eng = _engine(llama, slots=3, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16)
        stats0 = eng.warmup()
        rng = np.random.default_rng(35)
        shared = rng.integers(1, 250, 16).astype(np.int32)
        reqs = []
        for i in range(12):
            if i % 3 == 0:    # shared-prefix family (hits)
                ids = np.concatenate(
                    [shared, rng.integers(1, 250, 2 + i % 5)])
            elif i % 3 == 1:  # mid-block divergent family (COW)
                ids = np.concatenate(
                    [shared[:12], rng.integers(1, 250, 4 + i % 5)])
            else:             # growers (preemption pressure)
                ids = rng.integers(1, 250, 6)
            reqs.append(Request(prompt_ids=ids.astype(np.int32),
                                max_new_tokens=6 + (i % 3) * 5,
                                id=f"churn{i}"))
        for r in reqs:
            ok, reason = eng.submit(r)
            assert ok, reason
            eng.step()
        _drain(eng)
        for r in reqs:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
        s = eng.metrics.summary()
        assert s["prefix_hits"] > 0, "churn produced no prefix hits"
        assert s["cow_copies"] > 0, "churn produced no COW forks"
        assert s["preempted"] > 0, "churn produced no preemption"
        assert eng.compile_stats() == stats0, (
            "paged churn recompiled the engine")
        assert eng.mgr.reserved == 0
        assert eng.mgr.in_use == eng.prefix.evictable(), (
            "blocks leaked beyond the radix cache's retained prefixes")

    def test_prefix_cache_off_still_serves(self, llama):
        model, variables = llama
        eng = _engine(llama, prefix_cache=False)
        eng.warmup([9])
        req = Request(prompt_ids=_prompts([9], seed=40)[0],
                      max_new_tokens=4)
        eng.submit(req)
        _drain(eng)
        ref = np.asarray(generate(
            model, variables, jnp.asarray(req.prompt_ids)[None], 4,
        ))[0].tolist()
        assert req.tokens == ref
        s = eng.metrics.summary()
        assert s["prefix_lookups"] == 0 and s["prefix_hits"] == 0

    def test_reserve_admission_gates_on_block_demand(self, llama):
        """Under `reserve` admission a request whose worst-case block
        demand exceeds what's free waits in the queue (head-blocking
        FIFO) and admits once blocks free up — never a preemption."""
        eng = _engine(llama, slots=2, block_size=8, num_blocks=8,
                      queue_capacity=8)  # 7 usable blocks
        eng.warmup()
        rng = np.random.default_rng(41)
        # worst case 4 blocks each (8 prompt + 18 new = 26 tokens)
        r1 = Request(prompt_ids=rng.integers(1, 250, 8), max_new_tokens=18,
                     id="ra")
        r2 = Request(prompt_ids=rng.integers(1, 250, 8), max_new_tokens=18,
                     id="rb")
        eng.submit(r1)
        eng.submit(r2)
        eng.step()
        # only one fits its worst case (4 + 4 > 7): r2 must still queue
        assert eng.n_active == 1 and len(eng.queue) == 1
        _drain(eng)
        assert r1.status == "done" and r2.status == "done"
        assert eng.metrics.summary()["preempted"] == 0

    def test_deadline_fires_behind_block_gated_head(self, llama):
        """A block-gated head stalls admission, but deadlines queued
        behind it must still fire on time — the expiry sweep covers
        the whole queue, not just the popped prefix."""
        eng = _engine(llama, slots=2, block_size=8, num_blocks=8,
                      queue_capacity=8)
        eng.warmup()
        rng = np.random.default_rng(47)
        r0 = Request(prompt_ids=rng.integers(1, 250, 8), max_new_tokens=18,
                     id="gd0")
        big = Request(prompt_ids=rng.integers(1, 250, 8), max_new_tokens=18,
                      id="gd_big")  # worst case 4 blocks: gated
        doomed = Request(prompt_ids=rng.integers(1, 250, 4),
                         max_new_tokens=2, deadline_s=0.01, id="gd_dl")
        eng.submit(r0)
        eng.step()                      # r0 occupies + reserves
        eng.submit(big)
        eng.submit(doomed)
        time.sleep(0.02)                # doomed's deadline passes
        eng.step()
        assert eng.n_active == 1        # a slot is free, big still gated
        assert big.status == "queued"
        assert doomed.status == "timed_out"
        _drain(eng)

    def test_undersized_pool_rejected_at_construction(self, llama):
        with pytest.raises(ValueError, match="num-blocks"):
            _engine(llama, block_size=8, num_blocks=4)  # < one request

    def test_hbm_per_request_tracks_actual_tokens(self, llama):
        """The memory win the paged design exists for: short requests
        in big slots hold blocks for their tokens, not slots x L."""
        from hyperion_tpu.models.llama import paged_cache_block_bytes

        model, _ = llama
        eng = _engine(llama, slots=3, block_size=8)
        eng.warmup()
        eng.submit(Request(prompt_ids=_prompts([6], seed=44)[0],
                           max_new_tokens=16))
        eng.step()
        # one active request, 6 prompt tokens -> 1 block (not 6 = L/bs)
        assert eng.mgr.in_use == 1
        bb = paged_cache_block_bytes(model.cfg, 8)
        g = eng.metrics.reg.snapshot()["gauges"]
        assert g["serve_blocks_in_use"] == 1
        assert abs(g["serve_hbm_per_req_mb"] - bb / 2**20) < 1e-9
        _drain(eng)


# ----------------------------------------------- tiered KV (host spill)


class TestTieredKV:
    """The PR-20 tentpole drill, engine half: radix eviction DEMOTES
    chains to the host tier (serve/hostcache.py), a same-prefix re-hit
    restores them through the existing COW/scatter path with
    `tier=host` counted, the restored stream is temp-0 bit-identical
    to `generate`, `compile_stats()` stays flat across the whole
    evict→spill→restore cycle, and the store's serialized form feeds a
    SECOND engine the same hit after a restart. Geometry reuses the
    paged-churn shapes (slots 3, max_len 48, block_size 8, num_blocks
    8, optimistic) so the class adds zero jit compiles to tier-1."""

    def _tiered(self, llama, tmp_path, **kw):
        cfg = dict(slots=3, block_size=8, num_blocks=8,
                   admission="optimistic", queue_capacity=16,
                   host_cache_mb=8,
                   host_cache_dir=str(tmp_path / "hostcache"))
        cfg.update(kw)
        return _engine(llama, **cfg)

    def test_evict_spill_restore_bit_identical_zero_compiles(
            self, tmp_path, llama):
        model, variables = llama
        eng = self._tiered(llama, tmp_path)
        stats0 = eng.warmup()
        rng = np.random.default_rng(83)
        shared = rng.integers(1, 250, 16).astype(np.int32)

        # phase 1 — seed: a shared-prefix request leaves its two full
        # blocks retained by the radix cache
        seed_req = Request(prompt_ids=np.concatenate(
            [shared, rng.integers(1, 250, 3).astype(np.int32)]),
            max_new_tokens=4, id="tk_seed")
        ok, reason = eng.submit(seed_req)
        assert ok, reason
        _drain(eng)
        assert eng.prefix.evictable() >= 2

        # phase 2 — pressure: growers overflow the 7-usable-block pool,
        # so LRU eviction fires and the dying chain spills to host RAM
        # instead of being deleted
        growers = [Request(prompt_ids=rng.integers(1, 250, 6),
                           max_new_tokens=12, id=f"tk_gr{i}")
                   for i in range(3)]
        for r in growers:
            ok, reason = eng.submit(r)
            assert ok, reason
            eng.step()
        _drain(eng)
        s = eng.metrics.summary()
        assert s["host_spilled_blocks"] >= 2, s
        assert len(eng.host) >= 2

        # phase 3 — re-hit: same system prompt, different tail; the
        # device walk misses (the chain was evicted), the host walk
        # restores it, and the stream is bit-identical anyway
        rehit = Request(prompt_ids=np.concatenate(
            [shared, rng.integers(1, 250, 4).astype(np.int32)]),
            max_new_tokens=4, id="tk_rehit")
        ok, reason = eng.submit(rehit)
        assert ok, reason
        _drain(eng)
        s = eng.metrics.summary()
        assert s["tier_hits_host"] >= 1, s
        assert s["host_restored_blocks"] >= 2, s
        assert s["tier_hit_rate_host"] > 0
        assert s["restore_bytes_per_s"] > 0
        # the restore replaced a 16-token re-prefill
        assert s["prefill_tokens_saved"] >= 16
        for r in [seed_req, rehit] + growers:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
        # the whole evict→spill→restore cycle is eager host/device
        # traffic: not one new executable in either jit cache
        assert eng.compile_stats() == stats0, (
            "the host tier recompiled the engine")

        # phase 4 — restart survival: the drain serializes the store;
        # a SECOND engine (fresh radix, fresh pool) loads it and serves
        # the same prefix from host RAM without ever having decoded it
        eng.run()   # idle → immediate drain: saves <dir>/hostcache
        assert (tmp_path / "hostcache" / "index.json").exists()
        eng2 = self._tiered(llama, tmp_path)
        assert eng2.warmup() == stats0
        assert len(eng2.host) >= 2   # loaded at construction
        surv = Request(prompt_ids=np.concatenate(
            [shared, rng.integers(1, 250, 5).astype(np.int32)]),
            max_new_tokens=4, id="tk_surv")
        ok, reason = eng2.submit(surv)
        assert ok, reason
        _drain(eng2)
        s2 = eng2.metrics.summary()
        assert s2["tier_hits_host"] >= 1, s2
        ref = np.asarray(generate(
            model, variables, jnp.asarray(surv.prompt_ids)[None],
            4))[0].tolist()
        assert surv.tokens == ref, f"restart re-hit diverged: {surv.tokens}"
        assert eng2.compile_stats() == stats0

    def test_tier_off_by_default_and_ledger_reports_host(
            self, tmp_path, llama):
        assert EngineConfig(slots=3, max_len=48).host_cache_mb == 0
        eng = _engine(llama, slots=3, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16)
        assert eng.host is None
        led = _engine(llama, slots=3, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16,
                      host_cache_mb=8).memory_ledger()
        assert led["host_cache_budget_mb"] == 8
        assert led["host_cache_mb"] == 0.0   # nothing spilled yet


# ------------------------------------------------------ queue policy


class TestAdmissionQueue:
    def test_backpressure_rejects_with_reason(self):
        q = AdmissionQueue(2, max_total_tokens=32)
        r = [Request(prompt_ids=np.arange(1, 5), max_new_tokens=4)
             for _ in range(3)]
        assert q.submit(r[0]) == (True, None)
        assert q.submit(r[1]) == (True, None)
        ok, reason = q.submit(r[2])
        assert not ok and reason == REJECT_QUEUE_FULL
        assert r[2].status == "rejected"

    def test_too_long_rejected_at_the_door(self):
        q = AdmissionQueue(4, max_total_tokens=16)
        ok, reason = q.submit(
            Request(prompt_ids=np.arange(1, 13), max_new_tokens=8))
        assert not ok and reason == REJECT_TOO_LONG

    def test_deadline_drops_at_pop(self):
        q = AdmissionQueue(4, max_total_tokens=64)
        fast = Request(prompt_ids=np.arange(1, 4), max_new_tokens=2,
                       deadline_s=0.01)
        slow = Request(prompt_ids=np.arange(1, 4), max_new_tokens=2)
        q.submit(fast)
        q.submit(slow)
        admit, expired = q.pop_ready(2, now=fast.submitted_at + 1.0)
        assert expired == [fast] and fast.status == "timed_out"
        assert admit == [slow]

    def test_prefill_budget_caps_a_round(self):
        """Three 10-token prompts against a 16-token budget: round one
        admits one (10 > remaining 6 stops the second), so decode
        ticks interleave with prefills instead of waiting for all."""
        q = AdmissionQueue(8, max_total_tokens=64, prefill_budget=16)
        rs = [Request(prompt_ids=np.arange(1, 11), max_new_tokens=2)
              for _ in range(3)]
        for r in rs:
            q.submit(r)
        admit1, _ = q.pop_ready(3)
        assert admit1 == [rs[0]]
        admit2, _ = q.pop_ready(3)
        assert admit2 == [rs[1]]

    def test_oversized_head_still_admits_alone(self):
        """A prompt larger than the whole budget must not starve: it
        admits when it reaches the head, alone in its round."""
        q = AdmissionQueue(8, max_total_tokens=64, prefill_budget=8)
        big = Request(prompt_ids=np.arange(1, 33), max_new_tokens=2)
        q.submit(big)
        admit, _ = q.pop_ready(2)
        assert admit == [big]


# -------------------------------------------------- per-slot sampling


class TestPerSlotSampling:
    def test_single_request_path_pinned(self):
        """The satellite contract: extracting the top-k/top-p helpers
        left `sample_token` byte-identical — checked against an inline
        copy of the pre-refactor algorithm."""
        rng = np.random.default_rng(11)
        logits = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
        key = jax.random.key(5)

        def reference(logits, rng_key, temperature, top_k, top_p):
            logits = logits / temperature
            if top_k > 0:
                kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                order = jnp.argsort(-logits, axis=-1)
                sl = jnp.take_along_axis(logits, order, axis=-1)
                probs = jax.nn.softmax(sl, axis=-1)
                mass_before = jnp.cumsum(probs, axis=-1) - probs
                kept = jnp.where(mass_before < top_p, sl, -jnp.inf)
                logits = jnp.full_like(logits, -jnp.inf).at[
                    jnp.arange(logits.shape[0])[:, None], order
                ].set(kept)
            return jax.random.categorical(
                rng_key, logits, axis=-1).astype(jnp.int32)

        for t, k, p in ((0.8, 0, 1.0), (1.2, 5, 1.0), (0.7, 0, 0.9),
                        (1.0, 8, 0.85)):
            np.testing.assert_array_equal(
                np.asarray(sample_token(logits, key, t, k, p)),
                np.asarray(reference(logits, key, t, k, p)),
            )
        # greedy path
        np.testing.assert_array_equal(
            np.asarray(sample_token(logits, None)),
            np.asarray(jnp.argmax(logits, -1).astype(jnp.int32)),
        )

    def test_greedy_rows_match_sample_token(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        keys = jax.random.split(jax.random.key(0), 4)
        out = sample_token_slots(
            logits, keys,
            jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.float32),
        )
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(sample_token(logits, None)))

    def test_per_row_top_k_restricts_support(self):
        # row 0: top_k=2 over a spiked distribution; row 1: greedy
        logits = jnp.asarray([[10.0, 5.0, -100.0, -100.0],
                              [0.0, 1.0, 9.0, 0.0]])
        temps = jnp.asarray([1.0, 0.0], jnp.float32)
        ks = jnp.asarray([2, 0], jnp.int32)
        ps = jnp.ones((2,), jnp.float32)
        for seed in range(8):
            keys = jax.random.split(jax.random.key(seed), 2)
            out = np.asarray(sample_token_slots(
                logits, keys, temps, ks, ps))
            assert out[0] in (0, 1)
            assert out[1] == 2

    def test_per_row_top_p_restricts_support(self):
        # softmax([5,2,1,0]) puts ~93% on token 0: p=0.5 keeps only it.
        # Row 1 is nearly flat, so 16 draws over its full support land
        # on one token with probability ~1e-9 (a 93% mode did so with
        # 31%: the test passed or failed by the PRNG's luck).
        logits = jnp.asarray([[5.0, 2.0, 1.0, 0.0],
                              [0.3, 0.2, 0.1, 0.0]])
        temps = jnp.ones((2,), jnp.float32)
        ks = jnp.zeros((2,), jnp.int32)
        ps = jnp.asarray([0.5, 1.0], jnp.float32)
        seen_row1 = set()
        for seed in range(16):
            keys = jax.random.split(jax.random.key(seed), 2)
            out = np.asarray(sample_token_slots(
                logits, keys, temps, ks, ps))
            assert out[0] == 0
            seen_row1.add(int(out[1]))
        assert len(seen_row1) > 1  # p=1.0 row keeps the full support

    # what six rows ask for -> the tier the call must run: every row
    # greedy; temperature only; one row's top_k; one row's top_p; both
    # on several rows; and one restricted row beside greedy and
    # temperature-only rows (a greedy row's top_k / top_p are never
    # read, as `sample_token` never reads them)
    _T = [0.7, 0.0, 1.3, 0.0, 0.9, 1.0]
    TIER_CASES = {
        "greedy": ([0.0] * 6, [0] * 6, [1.0] * 6, 0),
        "greedy_stale_restrictions": ([0.0] * 6, [5] * 6, [0.5] * 6, 0),
        "temperature": (_T, [0] * 6, [1.0] * 6, 1),
        "top_k": (_T, [0, 0, 5, 0, 0, 0], [1.0] * 6, 2),
        "top_p": (_T, [0] * 6, [1.0, 1.0, 1.0, 1.0, 0.8, 1.0], 2),
        "both": (_T, [0, 3, 40, 0, 7, 0],
                 [0.9, 1.0, 0.5, 0.3, 0.8, 1.0], 2),
        "mixed_one_restricted": ([0.0, 0.0, 1.1, 0.0, 0.8, 0.0],
                                 [0, 0, 0, 9, 4, 0],
                                 [1.0, 0.4, 1.0, 1.0, 0.7, 1.0], 2),
    }

    @staticmethod
    def _parent_slots(logits, keys, temperature, top_k, top_p):
        """`sample_token_slots` as it stood before the tiers (PR 24):
        the whole sampled path for every row, whatever the rows ask."""
        from hyperion_tpu.infer.generate import _mask_top_p

        V = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = temperature.astype(logits.dtype)
        scaled = logits / jnp.where(t > 0, t, 1.0)[:, None]
        k = jnp.clip(top_k, 0, V)
        sorted_desc = -jnp.sort(-scaled, axis=-1)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
        restricted = jnp.where(
            (k > 0)[:, None] & (scaled < kth), -jnp.inf, scaled)
        restricted = _mask_top_p(restricted, top_p[:, None])
        sampled = jax.vmap(jax.random.categorical)(keys, restricted)
        return jnp.where(t > 0, sampled.astype(jnp.int32), greedy)

    @staticmethod
    def _switch_index(*args, **kw):
        """The index `sample_token_slots` hands its `lax.switch` for
        these arguments: the tier the device would run."""
        closed = jax.make_jaxpr(
            lambda *a: sample_token_slots(*a, **kw))(*args)
        eqns = closed.jaxpr.eqns
        (at,) = [i for i, e in enumerate(eqns)
                 if e.primitive.name == "cond"]
        index = closed.jaxpr.replace(
            eqns=eqns[:at], outvars=[eqns[at].invars[0]])
        (tier,) = jax.core.eval_jaxpr(index, closed.consts, *args)
        return int(tier)

    def _tier_inputs(self, case, rows=6, vocab=257):
        temps, ks, ps, tier = self.TIER_CASES[case]
        rng = np.random.default_rng(17)
        logits = jnp.asarray(rng.normal(size=(rows, vocab)), jnp.float32)
        keys = jax.random.split(jax.random.key(23), rows)
        return (logits, keys, jnp.asarray(temps, jnp.float32),
                jnp.asarray(ks, jnp.int32),
                jnp.asarray(ps, jnp.float32)), tier

    @pytest.mark.parametrize("case", sorted(TIER_CASES))
    def test_every_tier_yields_sample_token_row_for_row(self, case):
        """Whatever tier the rows' parameters select, each row's token
        is `sample_token`'s for that row with that row's key, and the
        whole call equals the path that sorted for every row."""
        args, tier = self._tier_inputs(case)
        logits, keys, temps, ks, ps = args
        assert int(sampling_tier(temps, ks, ps)) == tier
        assert self._switch_index(*args) == tier
        out = np.asarray(jax.jit(sample_token_slots)(*args))
        want = [int(sample_token(
            logits[i:i + 1], keys[i], float(temps[i]), int(ks[i]),
            float(ps[i]))[0]) for i in range(logits.shape[0])]
        assert out.tolist() == want
        np.testing.assert_array_equal(
            out, np.asarray(self._parent_slots(*args)))
        if tier:
            # the draw is a draw: some sampling row left its argmax
            assert (out != np.asarray(jnp.argmax(logits, -1))).any()

    @pytest.mark.parametrize("case", ["both", "mixed_one_restricted",
                                      "temperature"])
    def test_dead_lanes_cannot_lift_the_tier(self, case):
        """A freed slot keeps its last request's parameters in the
        engine's state: with the live mask, rows that are not live are
        greedy and the tier is that of the live rows alone."""
        args, tier = self._tier_inputs(case)
        logits, keys, temps, ks, ps = args
        assert tier > 0
        greedy_rows = np.asarray(temps) <= 0
        # only the greedy rows live: the stale sampled rows lift nothing
        live = jnp.asarray(greedy_rows)
        assert self._switch_index(*args, live=live) == 0
        out = np.asarray(sample_token_slots(*args, live=live))
        np.testing.assert_array_equal(
            out, np.asarray(jnp.argmax(logits, -1)))
        # every row live: the mask changes nothing
        everyone = jnp.ones_like(live)
        assert self._switch_index(*args, live=everyone) == tier
        np.testing.assert_array_equal(
            np.asarray(sample_token_slots(*args, live=everyone)),
            np.asarray(sample_token_slots(*args)))
        # the restricted rows dead, a temperature-only row live: tier 1
        unrestricted = (np.asarray(temps) > 0) & (np.asarray(ks) == 0) \
            & (np.asarray(ps) >= 1.0)
        if unrestricted.any() and tier == 2:
            live = jnp.asarray(unrestricted | greedy_rows)
            assert self._switch_index(*args, live=live) == 1
            got = np.asarray(sample_token_slots(*args, live=live))
            want = np.asarray(sample_token_slots(*args))
            np.testing.assert_array_equal(got[np.asarray(live)],
                                          want[np.asarray(live)])

    def test_engine_temperature_deterministic_per_seed(self, llama):
        """Same seed → same sampled continuation across engine runs
        (per-slot keys fold in the position, not wall clock)."""
        outs = []
        for _ in range(2):
            eng = _engine(llama)
            eng.warmup([8])
            req = Request(prompt_ids=_prompts([6], seed=9)[0],
                          max_new_tokens=6, temperature=0.9, top_k=12,
                          seed=42)
            eng.submit(req)
            _drain(eng)
            outs.append(req.tokens)
        assert outs[0] == outs[1]
        assert all(0 <= t < 256 for t in outs[0])


# ----------------------------------------------------- sampling tiers


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            x = getattr(x, "jaxpr", x)   # ClosedJaxpr -> Jaxpr
            if hasattr(x, "eqns"):
                yield x


class TestSamplingTiers:
    """ISSUE 25: the tick's sampling work follows what its live rows
    ask for, by a conditional inside the one executable."""

    def _programs(self, llama):
        import functools

        from hyperion_tpu.serve.engine import _spec_tick_impl, _tick_impl

        eng = _engine(llama, slots=3, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16,
                      spec_k=4, draft="ngram")
        args = (eng.variables, eng._cache, eng._state,
                jnp.asarray(eng._bt), jnp.asarray(eng._live_mask()))
        drafts = jnp.zeros((3, 4), jnp.int32)
        static = (eng.model, eng.cfg.eos_id, eng.cfg.pad_id)
        return {
            "tick": (functools.partial(_tick_impl, *static), args,
                     eng._tick_jit.lower(*static, *args)),
            "spec_tick": (functools.partial(_spec_tick_impl, *static),
                          args + (drafts,),
                          eng._spec_jit.lower(*static, *args, drafts)),
        }

    @pytest.mark.parametrize("program", ["tick", "spec_tick"])
    def test_one_conditional_whose_heavy_branch_alone_sorts(
            self, llama, program):
        """The lowered program holds ONE conditional: its first branch
        is empty (the argmax computed outside it), its second draws and
        does not sort, its third alone sorts. Under the speculative
        tick's `vmap` over window positions it is still a conditional:
        a batched index would have made it a select that runs all
        three."""
        fn, args, lowered = self._programs(llama)[program]
        text = lowered.as_text()
        assert text.count("stablehlo.case") == 1
        assert "stablehlo.sort" in text
        eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
        (cond,) = [e for e in eqns if e.primitive.name == "cond"]
        greedy, draw, restrict = (
            [e.primitive.name for e in _eqns(b.jaxpr)]
            for b in cond.params["branches"])
        assert greedy == []
        assert "sort" not in draw and "random_bits" in draw
        assert restrict.count("sort") >= 2 and "random_bits" in restrict
        assert "scatter" in restrict and "scatter" not in draw
        # and nothing outside the conditional sorts or draws
        n_all = [e.primitive.name for e in eqns]
        assert n_all.count("sort") == restrict.count("sort")
        assert n_all.count("random_bits") == \
            draw.count("random_bits") + restrict.count("random_bits")

    def _serve(self, eng, reqs):
        for r in reqs:
            ok, reason = eng.submit(r)
            assert ok, reason
        _drain(eng)
        return {r.id: list(r.tokens) for r in reqs}

    def _req(self, name, seed, **sampling):
        return Request(prompt_ids=_prompts([7], seed=seed)[0],
                       max_new_tokens=9, id=name, seed=seed, **sampling)

    SAMPLED = {
        "temperature": dict(temperature=0.9),
        "top_k": dict(temperature=0.9, top_k=12),
        "top_p": dict(temperature=1.1, top_p=0.8),
        "both": dict(temperature=1.3, top_k=40, top_p=0.9),
    }

    @pytest.mark.parametrize("kind", sorted(SAMPLED))
    def test_sampled_request_alone_and_beside_greedy_rows(
            self, llama, kind):
        """A seeded sampled request yields the same tokens alone (its
        own tier on every tick) and beside greedy rows (which it lifts
        into its tier); the greedy rows yield what they yield alone
        (the argmax alone). One engine serves all three mixes and
        compiles nothing after warm-up."""
        eng = _engine(llama)
        stats0 = eng.warmup([8])

        def greedy():
            return [self._req(f"g{i}", 30 + i) for i in (0, 1)]

        def sampled():
            return self._req("s", 77, **self.SAMPLED[kind])

        g_alone = self._serve(eng, greedy())
        tiers = eng.tickprof.snapshot()["sampling_tiers"]
        assert tiers["greedy"] == tiers["ticks"] > 0
        s_alone = self._serve(eng, [sampled()])
        mixed = self._serve(eng, greedy() + [sampled()])
        assert mixed["s"] == s_alone["s"]
        assert {k: mixed[k] for k in g_alone} == g_alone
        # a draw, not the argmax under another name
        model, variables = llama
        ref = np.asarray(generate(
            model, variables, jnp.asarray(_prompts([7], seed=77)[0])[None],
            9))[0].tolist()
        assert s_alone["s"] != ref
        assert eng.compile_stats() == stats0, (
            "a sampled request after greedy ones recompiled the engine")

    def test_tick_record_counts_the_rows_that_sample_and_restrict(
            self, llama, tmp_path):
        """`sampling_rows` / `restricted_rows` in the tick record's `c`
        come from the live requests' own parameters; `tickprof` rolls
        them up into ticks by tier, and `obs doctor` says in words when
        ticks sorted the vocabulary."""
        from hyperion_tpu.obs import doctor

        eng = _engine(llama)
        eng.warmup([8])
        reqs = [self._req("g", 1),
                self._req("t", 2, temperature=0.8),
                self._req("r", 3, temperature=0.8, top_p=0.9)]
        reqs[2].max_new_tokens = 4   # leaves first: the tier falls back
        for r in reqs:
            eng.submit(r)
        seen = []
        while not eng.idle:
            eng.step()
            rec = eng.tickprof.tail(1)[0]
            assert "device" in rec["s"]
            seen.append((rec["c"]["sampling_rows"],
                         rec["c"]["restricted_rows"]))
        assert seen[0] == (2, 1) and seen[-1] == (1, 0)
        assert set(seen) == {(2, 1), (1, 0)}
        snap = eng.tickprof.snapshot()
        n_sorted = seen.count((2, 1))
        assert snap["sampling_tiers"] == {
            "ticks": len(seen), "greedy": 0,
            "drawn": len(seen) - n_sorted, "sorted": n_sorted,
            "restricted_rows": [1, 1]}
        assert eng.exposition()["tickprof"]["sampling_tiers"] == \
            snap["sampling_tiers"]
        (tmp_path / "telemetry.jsonl").write_text(json.dumps(
            {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
             "tickprof": snap}) + "\n")
        row = next(ln for ln in doctor.render_markdown(
                       doctor.diagnose(tmp_path)).splitlines()
                   if ln.startswith("| host tick profile"))
        assert (f"{n_sorted} of {len(seen)} ticks sorted the vocabulary "
                "for 1 restricted row(s)") in row
        # an all-greedy window says nothing of the kind
        self._serve(eng, [self._req("g2", 4)])
        last = eng.tickprof.tail(1)[0]["c"]
        assert (last["sampling_rows"], last["restricted_rows"]) == (0, 0)


# --------------------------------------------------------- telemetry


class TestServeTelemetry:
    def _run_serve(self, tmp_path, llama, n=4):
        from hyperion_tpu.obs.heartbeat import Heartbeat
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="serve_t")
        hb = Heartbeat(tmp_path / "heartbeat.json", run="serve_t",
                       every=1)
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None,
                                  snapshot_every=4),
                     tracer=tracer, heartbeat=hb)
        eng.warmup([8])
        for i, p in enumerate(_prompts([6] * n, seed=1)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=5,
                               id=f"t{i}"))
        summary = eng.run()
        tracer.close()
        return summary

    def test_summarize_doctor_diff_consume_serve_stream(
            self, tmp_path, llama):
        """The acceptance criterion: a serve run's stream feeds all
        three obs tools with zero modification flags."""
        from hyperion_tpu.obs import diff as obs_diff
        from hyperion_tpu.obs import doctor, report

        self._run_serve(tmp_path, llama)
        s = report.summarize(tmp_path / "telemetry.jsonl")
        assert not s.get("error")
        assert s["steps"] > 0  # serve_tick spans count as steps
        assert s["tokens_per_s"] is not None

        d = doctor.diagnose(tmp_path)
        assert d["verdict"] == "healthy", d["reason"]
        assert d["serve"] is not None
        assert d["serve"]["completed"] == 4
        assert d["serve"]["ttft_p50_ms"] is not None
        md = doctor.render_markdown(d)
        assert "serve requests" in md and "TTFT" in md

        a = obs_diff.load_summary(tmp_path / "telemetry.jsonl")
        dd = obs_diff.diff(a, a)
        assert dd["comparable_metrics"] > 0
        assert dd["regressions"] == []

    def test_trace_round_trip_and_attribution(self, tmp_path, llama):
        """`obs trace` consumes a REAL engine stream (not a fixture):
        every request reconstructs with its lifecycle events, the
        phase totals partition e2e, and the Chrome export is
        non-empty. Shapes match `_run_serve`, so nothing recompiles."""
        from hyperion_tpu.obs import timeline
        from hyperion_tpu.obs.report import read_records

        self._run_serve(tmp_path, llama)
        records = read_records(tmp_path / "telemetry.jsonl")
        names = {r.get("name") for r in records
                 if r.get("kind") == "event"}
        assert {"request_admitted", "request_scheduled",
                "request_first_token", "request_finished"} <= names
        reqs = timeline.requests_from_records(records)
        done = [r for r in reqs if r.status == "done"]
        assert len(done) == 4
        for r in done:
            assert r.e2e_s is not None and r.e2e_s > 0
            assert r.phases["prefill"] > 0
            # phases never over-attribute, and the unexplained
            # remainder stays a minority (generous bound: CI boxes
            # under parallel load jitter hard)
            assert sum(r.phases.values()) <= r.e2e_s + 1e-6
            assert r.other_s < max(0.5 * r.e2e_s, 0.05)
        att = timeline.attribution(reqs)
        assert att["rows"]
        for row in att["rows"]:
            total = sum(row["components_ms"].values()) + row["other_ms"]
            assert total == pytest.approx(row["value_ms"], abs=0.02)
        assert timeline.chrome_trace(reqs, records)["traceEvents"]

    def test_slow_sink_charged_to_client_write(self, tmp_path, llama):
        """A slow CLIENT must show up as client_write in its own
        request's attribution — not inflate the decode phase and send
        an operator hunting a device problem that isn't there."""
        from hyperion_tpu.obs import timeline
        from hyperion_tpu.obs.report import read_records
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="slow_sink")
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None),
                     tracer=tracer)
        eng.warmup([8])
        req = Request(prompt_ids=_prompts([6], seed=2)[0],
                      max_new_tokens=4, id="slow",
                      sink=lambda ev: time.sleep(0.005))
        eng.submit(req)
        _drain(eng)
        tracer.close()
        assert req.client_write_s >= 0.015  # ≥4 writes × 5 ms, minus slop
        reqs = timeline.requests_from_records(
            read_records(tmp_path / "telemetry.jsonl"))
        (rt,) = [r for r in reqs if r.id == "slow"]
        assert rt.phases["client_write"] >= 0.015
        # decode is netted of sink time: both can't claim the same ms
        assert rt.phases["decode"] + rt.phases["client_write"] \
            <= rt.e2e_s + 1e-6
        att = timeline.attribution(reqs)
        e2e99 = next(r for r in att["rows"]
                     if r["metric"] == "e2e" and r["q"] == 99)
        assert e2e99["dominant"] == "client_write"

    def test_rejections_counted_and_evented(self, tmp_path, llama):
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "t.jsonl", run="rej")
        eng = Engine(model, variables,
                     EngineConfig(slots=1, max_len=48, eos_id=None,
                                  queue_capacity=1),
                     tracer=tracer)
        eng.warmup([8])
        results = [
            eng.submit(Request(prompt_ids=p, max_new_tokens=4))
            for p in _prompts([6] * 3, seed=2)
        ]
        _drain(eng)
        tracer.close()
        assert [ok for ok, _ in results].count(False) >= 1
        snap = eng.metrics.reg.snapshot()["counters"]
        assert snap["serve_rejected"] >= 1
        assert snap[f"serve_rejected_{REJECT_QUEUE_FULL}"] >= 1
        recs = [json.loads(line)
                for line in (tmp_path / "t.jsonl").read_text().splitlines()]
        assert any(r.get("name") == "request_rejected"
                   and r.get("reason") == REJECT_QUEUE_FULL for r in recs)


# -------------------------------------------------------- chaos seam


class TestServeChaos:
    def test_stalled_engine_is_hung_drained_is_healthy(
            self, tmp_path, llama):
        """The serve half of the doctor contract: a serve loop that
        stopped beating with no serve_end reads hung; the same engine
        after a clean drain reads healthy."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.heartbeat import Heartbeat, read_heartbeat
        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.testing import chaos

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="chaos_serve")
        hb = Heartbeat(tmp_path / "heartbeat.json", run="chaos_serve",
                       every=1)
        plan = chaos.ChaosPlan(chaos.parse_plan("stall@tick=1:0.05"))
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None),
                     tracer=tracer, heartbeat=hb, chaos=plan)
        eng.warmup([8])
        eng.submit(Request(prompt_ids=_prompts([6])[0],
                           max_new_tokens=6))
        t0 = time.monotonic()
        for _ in range(3):  # steps only: no run() → no serve_end yet
            eng.step()
        assert time.monotonic() - t0 >= 0.05  # the stall fired
        assert "stall@tick=1:0.05" in plan._fired
        tracer.flush()

        # judged long after the last beat: hung (no terminal event)
        beat = read_heartbeat(tmp_path / "heartbeat.json")
        d = doctor.diagnose(tmp_path, now=beat["t_wall"] + 1000)
        assert d["verdict"] == "hung", d["reason"]

        # …and after a clean drain, the same stream reads healthy
        _drain(eng)
        eng.run()  # idle → immediate drain: emits serve_end + hb done
        tracer.close()
        d = doctor.diagnose(tmp_path, now=beat["t_wall"] + 1000)
        assert d["verdict"] == "healthy", d["reason"]

    def test_slow_client_seam_fires_in_delivery_path(self, llama):
        from hyperion_tpu.testing import chaos

        plan = chaos.ChaosPlan(chaos.parse_plan("slow_client@tick=0:0.05"))
        eng = _engine(llama)
        eng.chaos = plan
        eng.warmup([8])
        eng.submit(Request(prompt_ids=_prompts([6])[0],
                           max_new_tokens=3))
        t0 = time.monotonic()
        _drain(eng)
        assert time.monotonic() - t0 >= 0.05
        assert "slow_client@tick=0:0.05" in plan._fired

    def test_tick_faults_do_not_cross_units(self):
        """stall@step=N must never fire from the serve loop's on_tick
        (and vice versa): the two loops share the grammar, not the
        trigger."""
        from hyperion_tpu.testing import chaos

        plan = chaos.ChaosPlan(chaos.parse_plan("stall@step=1:5"))
        t0 = time.monotonic()
        plan.on_tick(1)  # must NOT sleep 5s
        assert time.monotonic() - t0 < 1.0
        assert not plan._fired
        plan2 = chaos.ChaosPlan(chaos.parse_plan("stall@tick=1:0.01"))
        plan2.on_step(1)
        assert not plan2._fired


# ------------------------------------------------------- transports


class TestJsonlServer:
    def test_stdin_round_trip_and_clean_drain(self, llama):
        from hyperion_tpu.serve.server import serve_jsonl

        eng = _engine(llama, slots=2)
        eng.warmup([8])
        lines = [
            json.dumps({"id": f"q{i}", "prompt_ids": list(range(2, 9)),
                        "max_new_tokens": 4})
            for i in range(3)
        ] + ["this is not json"]
        out = io.StringIO()
        summary = serve_jsonl(eng, io.StringIO("\n".join(lines) + "\n"),
                              out)
        recs = [json.loads(line) for line in out.getvalue().splitlines()]
        dones = [r for r in recs if r.get("event") == "done"]
        assert {r["id"] for r in dones} == {"q0", "q1", "q2"}
        assert all(r["n_tokens"] == 4 for r in dones)
        assert sum(1 for r in recs if r.get("event") == "error") == 1
        assert summary["completed"] == 3
        assert eng.idle  # clean drain

    def test_socket_round_trip(self, tmp_path, llama):
        import threading

        from hyperion_tpu.serve.client import ServeClient
        from hyperion_tpu.serve.server import serve_socket

        eng = _engine(llama, slots=2)
        eng.warmup([8])
        sock = str(tmp_path / "serve.sock")
        stop = threading.Event()
        ready = threading.Event()
        srv = threading.Thread(
            target=serve_socket, args=(eng, sock),
            kwargs={"should_stop": stop.is_set, "ready": ready},
            daemon=True,
        )
        srv.start()
        assert ready.wait(timeout=10)
        try:
            with ServeClient(sock, timeout_s=60) as c:
                res = c.generate(id="s1", prompt_ids=list(range(3, 9)),
                                 max_new_tokens=5)
            assert res["final"]["event"] == "done"
            assert len(res["tokens"]) == 5
            ref = np.asarray(generate(
                llama[0], llama[1],
                jnp.asarray(np.arange(3, 9, dtype=np.int32))[None], 5,
            ))[0].tolist()
            assert res["tokens"] == ref
        finally:
            stop.set()
            srv.join(timeout=30)
        assert not srv.is_alive()

    def test_smoke_script_invocations_parse(self):
        """Flag-drift guard for scripts/serve_smoke.sh (the
        capture-script pattern): its serve invocation must parse
        against the real server arg surface."""
        import re
        import shlex
        from pathlib import Path

        from hyperion_tpu.serve.server import build_parser

        script = (Path(__file__).resolve().parents[1] / "scripts"
                  / "serve_smoke.sh").read_text()
        script = re.sub(r"\\\n\s*", " ", script)
        calls = re.findall(r"python -m hyperion_tpu\.cli\.main serve\s+(.*)",
                           script)
        assert len(calls) >= 2, (
            "serve_smoke.sh lost a serve invocation (expected the basic "
            "round trip AND the shared-prefix one)")
        parsed = []
        for call in calls:
            toks = [t for t in shlex.split(call.split(">")[0])
                    if t != "|"]
            args = build_parser().parse_args(
                [re.sub(r"\$\{?\w+\}?", "x", t) for t in toks])
            assert args.slots >= 1
            parsed.append(args)
        # the prefix round trip really exercises the paged knobs
        assert any(a.block_size != 16 and a.prefix_cache for a in parsed)
        # and the speculative round trip really turns speculation on
        assert any(a.spec_k > 0 and a.draft == "ngram" for a in parsed), (
            "serve_smoke.sh lost the speculative round trip")
        # and the paged-attention round trip really switches the kernel
        assert any(a.paged_attn == "pallas" for a in parsed), (
            "serve_smoke.sh lost the --paged-attn pallas round trip")
        # and the tiered-KV round trip really turns the host tier on
        assert any(a.host_cache_mb > 0 for a in parsed), (
            "serve_smoke.sh lost the --host-cache-mb tiered-KV round "
            "trip")


# -------------------------------------------------------- load + soak


class TestLoadGenerator:
    def test_deterministic_report(self, llama):
        """Same spec + seed → same arrival schedule and prompt mix, so
        completed/token counts match across runs (latency numbers may
        wiggle; the workload must not). Queue capacity is generous on
        purpose: arrivals race the wall clock, and a capacity riding
        the edge of the drain rate would let scheduler jitter decide
        whether one request gets door-rejected — the backpressure path
        has its own tests (`test_all_rejected_load...`, the soak)."""
        reports = []
        for _ in range(2):
            eng = _engine(llama, slots=2, queue_capacity=16,
                          prefill_budget=32)
            spec = LoadSpec(n_requests=10, rate_hz=200.0,
                            prompt_lens=(4, 8), max_new=(3, 5),
                            vocab=250, seed=5)
            eng.warmup(list(spec.prompt_lens))
            reports.append(run_load(eng, spec))
        a, b = reports
        assert a["requests"] == b["requests"] == 10
        assert a["completed"] == b["completed"]
        assert a["tokens"] == b["tokens"]
        assert a["completed"] + a["rejected"] + a["timed_out"] == 10
        if a["completed"]:
            assert a["ttft_p50_ms"] is not None
            # the attribution keys ride every report
            for k in ("queue_wait_p99_ms", "prefill_p99_ms",
                      "decode_p99_ms", "preempt_replay_p99_ms",
                      "client_write_p99_ms"):
                assert a[k] is not None, k
            assert a["dominant_phase_p99"] is not None

    def test_all_rejected_load_still_reports(self, llama):
        """A spec whose every request is door-rejected (too_long) with
        nothing in flight must produce a report with reject_rate 1.0,
        not crash the driver off the end of the arrival schedule."""
        eng = _engine(llama, slots=2, max_len=48)
        eng.warmup([8])
        spec = LoadSpec(n_requests=3, rate_hz=100.0, prompt_lens=(60,),
                        max_new=(12,), vocab=250, seed=0)
        report = run_load(eng, spec)
        assert report["rejected"] == 3
        assert report["reject_rate"] == 1.0
        assert report["completed"] == 0 and report["tokens"] == 0

    def test_metrics_summary_reports_slos(self, llama):
        eng = _engine(llama, slots=2)
        eng.warmup([8])
        for p in _prompts([6] * 3, seed=4):
            eng.submit(Request(prompt_ids=p, max_new_tokens=4))
        eng.run()
        s = eng.metrics.summary()
        assert s["completed"] == 3
        assert s["ttft_ms"]["count"] == 3
        assert "p95" in s["ttft_ms"]  # SLO percentiles in every snapshot
        assert s["e2e_ms"]["count"] == 3
        # every delivered token counted, the prefill-sampled one included
        assert s["tokens"] == 12
        assert s["tokens_per_s"] and s["tokens_per_s"] > 0

    def test_shared_prefix_workload_exercises_prefix_cache(self, llama):
        """The loadgen satellite: --shared-prefix-tokens emits requests
        with a common system prompt, so the report's cache keys go
        green — hit rate and tokens saved above zero."""
        eng = _engine(llama, slots=2, block_size=8, queue_capacity=16,
                      prefill_budget=64)
        spec = LoadSpec(n_requests=8, rate_hz=500.0, prompt_lens=(3, 5),
                        max_new=(3, 4), vocab=250, seed=7,
                        shared_prefix_tokens=16)
        eng.warmup([21])  # shared prefix + longest tail
        report = run_load(eng, spec)
        assert report["shared_prefix_tokens"] == 16
        assert report["completed"] == 8
        assert report["prefix_hit_rate"] > 0
        assert report["prefill_tokens_saved"] > 0
        assert report["blocks_in_use"] is not None
        assert report["hbm_per_req_mb"] is not None
        # every request's prompt really starts with the same 16 tokens:
        # tokens saved must be at least (hits x full shared blocks)
        assert report["prefill_tokens_saved"] >= 7 * 16

    def test_doctor_reads_cache_pressure_evidence(self, tmp_path, llama):
        """The doctor satellite: a run that preempted through an
        undersized pool gets a cache-pressure note and a serve-cache
        evidence row, not just slow numbers."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="cache_p")
        eng = Engine(model, variables,
                     EngineConfig(slots=3, max_len=48, eos_id=None,
                                  block_size=8, num_blocks=10,
                                  admission="optimistic",
                                  prefix_cache=False),
                     tracer=tracer)
        eng.warmup()
        rng = np.random.default_rng(9)
        for i in range(3):
            eng.submit(Request(prompt_ids=rng.integers(1, 250, 8),
                               max_new_tokens=20, id=f"d{i}"))
        eng.run()
        tracer.close()
        assert eng.metrics.summary()["preempted"] > 0
        d = doctor.diagnose(tmp_path)
        assert d["verdict"] == "healthy"
        assert d["serve"]["preempted"] >= 1
        assert d["cache_pressure"], "no cache-pressure note"
        assert "--num-blocks" in d["reason"]
        md = doctor.render_markdown(d)
        assert "serve KV cache" in md and "cache pressure" in md

    def test_doctor_flags_zero_hits_under_shared_prefix(
            self, tmp_path, llama):
        """A shared-prefix workload served with the prefix cache off is
        a config bug the telemetry should name."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="zero_hits")
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None,
                                  block_size=64),  # block > shared prefix
                     tracer=tracer)
        spec = LoadSpec(n_requests=4, rate_hz=500.0, prompt_lens=(3,),
                        max_new=(3,), vocab=250, seed=3,
                        shared_prefix_tokens=16)
        eng.warmup([19])
        run_load(eng, spec)
        eng.run()  # idle -> immediate drain: serve_end lands
        tracer.close()
        d = doctor.diagnose(tmp_path)
        assert d["serve"]["prefix_hits"] in (0, None)
        assert any("ZERO prefix hits" in note
                   for note in d["cache_pressure"]), d["cache_pressure"]

# ------------------------------------------- crash safety (journal/PR 8)


class TestCrashReplay:
    """Journal + replay against a live engine. Every engine here uses
    the suite's already-compiled shapes (slots 2/3, max_len 48, buckets
    8/16) so nothing in this class adds a jit compile to tier-1."""

    def _streams_by_id(self, *streams):
        per: dict[str, list[int]] = {}
        for evs in streams:
            for ev in evs:
                if ev.kind == "token" and ev.token is not None:
                    per.setdefault(ev.request.id, []).append(ev.token)
        return per

    def test_crash_replay_bit_identical_and_exactly_once(
            self, tmp_path, llama):
        """The tentpole oracle, in-process: an engine abandoned
        mid-decode (the host-side equivalent of a kill — nothing is
        drained, closed, or flushed beyond the journal's own appends)
        is replaced by a fresh engine over the same journal; every
        request completes bit-identical to `generate`, and the UNION of
        both engines' client streams contains each token exactly once."""
        from hyperion_tpu.obs import timeline
        from hyperion_tpu.obs.report import read_records
        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.serve.journal import RequestJournal

        model, variables = llama
        jp = tmp_path / "journal.jsonl"
        eng1 = _engine(llama)
        eng1.journal = RequestJournal(jp)
        eng1.warmup([8, 16])
        s1: list = []
        prompts = _prompts([5, 9, 4], seed=13)
        reqs = [Request(prompt_ids=p, max_new_tokens=5 + i, id=f"cr{i}",
                        sink=s1.append)
                for i, p in enumerate(prompts)]
        for r in reqs:
            ok, reason = eng1.submit(r)
            assert ok, reason
        for _ in range(3):
            eng1.step()  # mid-decode; eng1 is now abandoned, unclosed

        tracer = Tracer(tmp_path / "telemetry.jsonl", run="replay_run")
        eng2 = Engine(model, variables,
                      EngineConfig(slots=3, max_len=48, eos_id=None),
                      tracer=tracer, journal=RequestJournal(jp))
        stats0 = eng2.warmup([8, 16])
        s2: list = []
        info = eng2.replay_pending(s2.append)
        assert info["resumed"] == 3 and info["poisoned"] == 0
        _drain(eng2)
        eng2.journal.close_clean()
        tracer.close()

        for i, r in enumerate(reqs):
            ref = np.asarray(generate(
                model, variables, jnp.asarray(prompts[i])[None],
                5 + i))[0].tolist()
            per = self._streams_by_id(s1, s2)
            assert per[f"cr{i}"] == ref, (
                f"cr{i}: stream {per[f'cr{i}']} != oracle {ref}")
        # replay never recompiled (same shapes, shared jit caches)
        assert eng2.compile_stats() == stats0
        # a clean journal owes nothing to the next life
        assert RequestJournal(jp).pending_count() == 0
        # the replay is visible to `obs trace` as a resumed request
        records = read_records(tmp_path / "telemetry.jsonl")
        assert any(r.get("name") == "serve_prefill" and r.get("resumed")
                   for r in records)
        rts = timeline.requests_from_records(records, run="replay_run")
        segs = {name for rt in rts for (name, _, _) in rt.segments}
        assert "replay_prefill" in segs

    def test_two_crashes_then_completion(self, tmp_path, llama):
        """Kill-twice-replay: two abandoned engines, the third
        completes — outputs bit-identical, streams duplicate-free."""
        from hyperion_tpu.serve.journal import RequestJournal

        model, variables = llama
        jp = tmp_path / "journal.jsonl"
        prompts = _prompts([6, 8], seed=17)
        budgets = [7, 6]
        streams: list[list] = []
        reqs = None
        for life in range(3):
            eng = _engine(llama)
            eng.journal = RequestJournal(jp)
            eng.warmup([8, 16])
            sink_list: list = []
            streams.append(sink_list)
            if life == 0:
                reqs = [Request(prompt_ids=p, max_new_tokens=budgets[i],
                                id=f"kt{i}", sink=sink_list.append)
                        for i, p in enumerate(prompts)]
                for r in reqs:
                    eng.submit(r)
            else:
                eng.replay_pending(sink_list.append)
            if life < 2:
                for _ in range(2):
                    eng.step()  # crash again mid-decode
            else:
                _drain(eng)
                eng.journal.close_clean()
        per = self._streams_by_id(*streams)
        for i, p in enumerate(prompts):
            ref = np.asarray(generate(
                model, variables, jnp.asarray(p)[None],
                budgets[i]))[0].tolist()
            assert per[f"kt{i}"] == ref, (per[f"kt{i}"], ref)
        assert RequestJournal(jp).pending_count() == 0

    def test_poisoned_replay_quarantines_with_event(self, tmp_path, llama):
        """A journal showing max_replays prior resumes for an
        unfinished request quarantines it: `request_poisoned` on the
        stream, a rejected wire event for the client, nothing
        re-admitted — the crash loop ends at the request, not the
        replica."""
        import json as json_mod

        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.serve.journal import RequestJournal
        from hyperion_tpu.serve.queue import REJECT_POISONED

        model, variables = llama
        jp = tmp_path / "journal.jsonl"
        j = RequestJournal(jp)
        j.admit(Request(prompt_ids=_prompts([6], seed=23)[0],
                        max_new_tokens=4, id="evil"))
        j.close()
        with jp.open("a") as f:  # two prior lives already replayed it
            f.write(json_mod.dumps({"k": "replay", "id": "evil", "n": 1})
                    + "\n")
            f.write(json_mod.dumps({"k": "replay", "id": "evil", "n": 2})
                    + "\n")
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="poison_run")
        eng = Engine(model, variables,
                     EngineConfig(slots=3, max_len=48, eos_id=None),
                     tracer=tracer, journal=RequestJournal(jp))
        got: list = []
        info = eng.replay_pending(got.append)
        tracer.close()
        assert info == {"resumed": 0, "finished": 0, "poisoned": 1,
                        "clean": False}
        assert len(eng.queue) == 0
        (ev,) = got
        assert ev.kind == "rejected" and ev.reason == REJECT_POISONED
        assert eng.metrics.summary()["poisoned"] == 1
        recs = [json_mod.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        assert any(r.get("name") == "request_poisoned"
                   and r.get("request") == "evil" for r in recs)
        # and the quarantine is durable: the next recovery skips it too
        resume, _, poisoned, _ = RequestJournal(jp).recover()
        assert resume == [] and poisoned == []


class TestSpeculative:
    """The PR-12 tentpole oracle: speculative decode (spec_k=4, n-gram
    self-draft) inside the engine stays bit-identical to `generate`
    under the WORST combination the serving layer offers — 12-request
    churn through an undersized optimistically-admitted pool (pool-
    exhaustion preemption) crossed with a mid-stream crash and journal
    replay — while the jit caches stay flat after warmup. Geometry
    reuses the paged-churn test's shapes (slots 3, max_len 48,
    block_size 8, num_blocks 8) so the only compile this class may add
    to tier-1 is the single [3, 4] spec-tick executable."""

    def _spec_engine(self, llama):
        return _engine(llama, slots=3, block_size=8, num_blocks=8,
                       admission="optimistic", queue_capacity=16,
                       spec_k=4, draft="ngram")

    def test_spec_oracle_churn_preemption_crash_replay(
            self, tmp_path, llama):
        from hyperion_tpu.serve.journal import RequestJournal

        model, variables = llama
        jp = tmp_path / "journal.jsonl"
        eng1 = self._spec_engine(llama)
        eng1.journal = RequestJournal(jp)
        before = eng1.compile_stats()
        stats0 = eng1.warmup()
        # the spec tick is ONE new executable; everything else reuses
        # the suite's already-warmed shapes (shared process-wide jits)
        assert stats0["spec_tick_executables"] \
            - before["spec_tick_executables"] == 1

        rng = np.random.default_rng(35)
        shared = rng.integers(1, 250, 16).astype(np.int32)
        s1: list = []
        reqs = []
        for i in range(12):
            if i % 3 == 0:    # shared-prefix family (drafts + hits)
                ids = np.concatenate(
                    [shared, rng.integers(1, 250, 2 + i % 5)])
            elif i % 3 == 1:  # mid-block divergent family (COW)
                ids = np.concatenate(
                    [shared[:12], rng.integers(1, 250, 4 + i % 5)])
            else:             # growers (preemption pressure)
                ids = rng.integers(1, 250, 6)
            reqs.append(Request(prompt_ids=ids.astype(np.int32),
                                max_new_tokens=6 + (i % 4) * 4,
                                id=f"spec{i}", sink=s1.append))
        for r in reqs:
            ok, reason = eng1.submit(r)
            assert ok, reason
        for _ in range(5):
            eng1.step()  # mid-stream: tokens already delivered
        # eng1 is abandoned here — nothing drained, closed, or flushed
        # beyond the journal's own per-token appends

        eng2 = self._spec_engine(llama)
        eng2.journal = RequestJournal(jp)
        stats1 = eng2.warmup()
        assert stats1 == stats0, "second life recompiled something"
        s2: list = []
        info = eng2.replay_pending(s2.append)
        assert info["poisoned"] == 0
        _drain(eng2)
        eng2.journal.close_clean()

        # union of both lives' streams: every token exactly once, and
        # the whole request bit-identical to the sequential oracle
        per: dict[str, list[int]] = {}
        for evs in (s1, s2):
            for ev in evs:
                if ev.kind == "token" and ev.token is not None:
                    per.setdefault(ev.request.id, []).append(ev.token)
        for r in reqs:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert per[r.id] == ref, (
                f"{r.id}: stream {per[r.id]} != oracle {ref}")
        assert eng2.compile_stats() == stats0, (
            "speculative churn recompiled the engine")
        m1, m2 = eng1.metrics.summary(), eng2.metrics.summary()
        assert m1["preempted"] + m2["preempted"] > 0, (
            "churn produced no pool-exhaustion preemption")
        assert m1["spec_drafted"] + m2["spec_drafted"] > 0
        # a clean journal owes nothing to the next life
        assert RequestJournal(jp).pending_count() == 0

    def test_spec_off_is_default_and_rejects_bad_config(self, llama):
        model, variables = llama
        assert EngineConfig(slots=3, max_len=48).spec_k == 0
        with pytest.raises(ValueError):
            Engine(model, variables,
                   EngineConfig(slots=3, max_len=48, spec_k=2,
                                draft="beam"))
        with pytest.raises(ValueError):
            Engine(model, variables,
                   EngineConfig(slots=3, max_len=48, spec_k=-1))


class TestDrain:
    def test_drain_under_load_finishes_owed_work(self, tmp_path, llama):
        """SIGTERM semantics (engine half): begin_drain closes the door
        — new submits reject with reason 'draining' — while in-flight
        AND already-queued requests run to completion; the journal
        closes clean, so the next start replays nothing."""
        from hyperion_tpu.serve.journal import RequestJournal
        from hyperion_tpu.serve.queue import REJECT_DRAINING

        jp = tmp_path / "journal.jsonl"
        eng = _engine(llama, slots=2)
        eng.journal = RequestJournal(jp)
        eng.warmup([8])
        reqs = [Request(prompt_ids=p, max_new_tokens=4, id=f"dr{i}")
                for i, p in enumerate(_prompts([6] * 4, seed=29))]
        for r in reqs:
            ok, reason = eng.submit(r)
            assert ok, reason
        eng.step()  # two in slots, two queued
        eng.begin_drain(timeout_s=30.0)
        assert eng.draining
        late = Request(prompt_ids=_prompts([6], seed=31)[0],
                       max_new_tokens=4, id="late")
        ok, reason = eng.submit(late)
        assert not ok and reason == REJECT_DRAINING
        summary = eng.run()  # drains: draining + idle breaks the loop
        assert summary["completed"] == 4
        assert all(r.status == "done" for r in reqs)
        assert eng.idle
        eng.journal.close_clean()
        assert RequestJournal(jp).pending_count() == 0

    def test_drain_timeout_leaves_work_journaled(self, tmp_path, llama):
        """A drain whose grace window closes with work still in hand
        stops anyway — and the unfinished requests stay on the journal
        for the next life instead of being lost."""
        from hyperion_tpu.serve.journal import RequestJournal

        jp = tmp_path / "journal.jsonl"
        eng = _engine(llama, slots=2)
        eng.journal = RequestJournal(jp)
        eng.warmup([8])
        for i, p in enumerate(_prompts([6] * 3, seed=37)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=40,
                               id=f"dt{i}"))
        eng.step()
        eng.begin_drain(timeout_s=0.0)  # already expired
        eng.run()
        assert not eng.idle  # work abandoned at the deadline...
        eng.journal.close()
        assert RequestJournal(jp).pending_count() == 3  # ...but owed


class TestBrownout:
    def test_shed_clamp_events_and_doctor_naming(self, tmp_path, llama):
        """Overload brownout end to end on a live engine: depth
        watermark trips the governor, deadline-doomed queued requests
        shed with reason shed_deadline, new admissions get their budget
        clamped (journal records the clamped value), hysteresis exits
        once the queue empties, and `obs doctor` names the incident."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.serve.queue import REJECT_SHED

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="brownout_run")
        eng = Engine(
            model, variables,
            EngineConfig(slots=2, max_len=48, eos_id=None,
                         queue_capacity=16, brownout=True,
                         brownout_depth=2, brownout_clamp=2),
            tracer=tracer)
        eng.warmup([8])
        rng_prompts = _prompts([6] * 4, seed=41)
        keepers = [Request(prompt_ids=p, max_new_tokens=3, id=f"bk{i}")
                   for i, p in enumerate(rng_prompts)]
        doomed = [Request(prompt_ids=p, max_new_tokens=3, id=f"bd{i}",
                          deadline_s=0.004)
                  for i, p in enumerate(_prompts([6] * 2, seed=43))]
        shed_events: list = []
        for r in keepers + doomed:
            r.sink = (lambda ev: shed_events.append(ev)
                      if ev.kind == "rejected" else None)
            ok, reason = eng.submit(r)
            assert ok, reason
        time.sleep(0.01)  # the doomed deadlines pass
        eng.step()  # depth 6 >= 2: enter + shed
        assert eng._governor.active
        s = eng.metrics.summary()
        assert s["shed"] == 2
        assert all(r.status == "rejected" for r in doomed)
        assert all(r.finish_reason == REJECT_SHED for r in doomed)
        # clamp while active: an 8-token ask is served at 2
        clamped = Request(prompt_ids=_prompts([6], seed=47)[0],
                          max_new_tokens=8, id="bclamp")
        ok, _ = eng.submit(clamped)
        assert ok
        _drain(eng)
        assert clamped.clamped_from == 8 and len(clamped.tokens) == 2
        assert not eng._governor.active  # hysteresis exited at depth 0
        summary = eng.run()  # idle: emits serve_end + final snapshot
        tracer.close()
        assert summary["brownout_clamped"] == 1
        assert summary["brownout_active"] is False

        d = doctor.diagnose(tmp_path)
        assert d["verdict"] == "healthy", d["reason"]
        assert d["overload"], "brownout produced no named incident"
        assert any("shed 2" in o for o in d["overload"])
        assert "serving robustness" in d["reason"]
        recs = [json.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        names = [r.get("name") for r in recs]
        assert "brownout_enter" in names and "brownout_exit" in names
        shed_recs = [r for r in recs if r.get("name") == "request_rejected"
                     and r.get("reason") == REJECT_SHED]
        assert len(shed_recs) == 2
        assert all(r.get("shed") and r.get("queued_s") is not None
                   for r in shed_recs)


class TestWorkloadIsolation:
    """SLO-class isolation drill (PR 14): a batch flood submitted
    AHEAD of interactive traffic must not win the TTFT race, overload
    must shed batch only, and none of the class machinery may perturb
    a single sampled token."""

    def test_isolation_drill_batch_flood(self, llama):
        from hyperion_tpu.serve.queue import (
            CLASS_BATCH, CLASS_INTERACTIVE, REJECT_SHED)

        model, variables = llama
        eng = _engine(llama, slots=2, queue_capacity=16, brownout=True,
                      brownout_depth=6, interactive_weight=3,
                      batch_weight=1)
        stats0 = eng.warmup([8, 16])
        batch_keep = [
            Request(prompt_ids=p, max_new_tokens=4, id=f"bk{i}",
                    sla_class=CLASS_BATCH, tenant="adv_burst")
            for i, p in enumerate(_prompts([6, 9, 5], seed=61))]
        batch_doomed = [
            Request(prompt_ids=p, max_new_tokens=4, id=f"bd{i}",
                    sla_class=CLASS_BATCH, tenant="adv_burst",
                    deadline_s=0.004)
            for i, p in enumerate(_prompts([7, 8], seed=62))]
        inter = [
            Request(prompt_ids=p, max_new_tokens=3 + i, id=f"iq{i}")
            for i, p in enumerate(_prompts([5, 8, 6, 9], seed=63))]
        # the hostile ordering: the whole batch flood is queued before
        # the first interactive request arrives
        for r in batch_keep + batch_doomed + inter:
            ok, reason = eng.submit(r)
            assert ok, reason
        time.sleep(0.01)  # doomed deadlines pass while queued
        _drain(eng)

        # sheds are batch-only; zero interactive requests were touched
        s = eng.metrics.summary()
        assert all(r.status == "rejected"
                   and r.finish_reason == REJECT_SHED
                   for r in batch_doomed)
        assert s["by_class"][CLASS_BATCH]["shed"] == 2
        assert s["by_class"][CLASS_INTERACTIVE]["shed"] == 0
        assert s["by_class"][CLASS_INTERACTIVE]["completed"] == len(inter)

        # weighted-fair admission won the TTFT race for interactive
        # even though every batch prompt was queued first
        ttft_i = s["by_class"][CLASS_INTERACTIVE]["ttft_ms"]["p99"]
        ttft_b = s["by_class"][CLASS_BATCH]["ttft_ms"]["p99"]
        assert ttft_i < ttft_b, (
            f"interactive TTFT p99 {ttft_i} not under batch {ttft_b}")

        # temp-0 bit-identity: class scheduling re-orders work, never
        # tokens — survivors of BOTH classes match `generate`
        for r in inter + batch_keep:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert r.tokens == ref, f"{r.id}: {r.tokens} != {ref}"
        assert eng.compile_stats() == stats0, (
            "class scheduling added an executable")

    def test_class_brownout_order_clamps_batch_only(self, llama):
        """The router's `class_brownout` control verb, exercised at the
        engine API: while ordered, batch admissions get their budget
        clamped as if the local governor were active; interactive is
        untouched; lifting the order restores batch."""
        from hyperion_tpu.serve.queue import (
            CLASS_BATCH, CLASS_INTERACTIVE)

        eng = _engine(llama, slots=2, queue_capacity=8,
                      brownout_clamp=2)
        eng.warmup([8])
        res = eng.control({"cmd": "class_brownout", "active": True})
        assert res["status"] == "ok" and res["changed"]
        b = Request(prompt_ids=_prompts([6], seed=71)[0],
                    max_new_tokens=8, id="cb_b", sla_class=CLASS_BATCH)
        i = Request(prompt_ids=_prompts([6], seed=72)[0],
                    max_new_tokens=8, id="cb_i")
        for r in (b, i):
            ok, reason = eng.submit(r)
            assert ok, reason
        res = eng.control({"cmd": "class_brownout", "active": False})
        assert res["status"] == "ok" and res["changed"]
        b2 = Request(prompt_ids=_prompts([6], seed=73)[0],
                     max_new_tokens=8, id="cb_b2",
                     sla_class=CLASS_BATCH)
        ok, reason = eng.submit(b2)
        assert ok, reason
        _drain(eng)
        assert b.clamped_from == 8 and len(b.tokens) == 2
        assert i.clamped_from is None and len(i.tokens) == 8
        assert b2.clamped_from is None and len(b2.tokens) == 8
        s = eng.metrics.summary()
        assert s["by_class"][CLASS_BATCH]["clamped"] == 1
        assert s["by_class"][CLASS_INTERACTIVE]["clamped"] == 0


class TestChunkedPrefill:
    """Chunked prefill (PR 14): long prompts stream through the cache
    in fixed chunks interleaved with decode. One static chunk shape is
    exactly one executable, and chunking survives the full gauntlet —
    prefix hits, preemption, and a mid-flight crash replay — with
    every output still bit-identical to `generate`."""

    def test_chunked_churn_preemption_replay_bit_identical(
            self, tmp_path, llama):
        from hyperion_tpu.serve.journal import RequestJournal

        model, variables = llama
        jp = tmp_path / "journal.jsonl"

        def make(journal):
            eng = _engine(llama, slots=3, block_size=8, num_blocks=8,
                          admission="optimistic", queue_capacity=16,
                          prefill_chunk=16)
            eng.journal = journal
            return eng

        eng1 = make(RequestJournal(jp))
        stats0 = eng1.warmup()
        assert stats0["chunk_executables"] == 1, stats0
        rng = np.random.default_rng(77)
        shared = rng.integers(1, 250, 18).astype(np.int32)
        s1: list = []
        reqs = []
        for i in range(12):
            if i % 3 == 0:    # long + shared prefix: chunked, hits
                ids = np.concatenate(
                    [shared, rng.integers(1, 250, 4 + i % 7)])
            elif i % 3 == 1:  # long, divergent: chunked, COW pressure
                ids = rng.integers(1, 250, 17 + i % 9)
            else:             # short growers: one-shot prefill path,
                ids = rng.integers(1, 250, 5)  # preemption pressure
            reqs.append(Request(prompt_ids=ids.astype(np.int32),
                                max_new_tokens=5 + (i % 3) * 4,
                                id=f"ch{i}", sink=s1.append))
        for r in reqs:
            ok, reason = eng1.submit(r)
            assert ok, reason
            eng1.step()
        for _ in range(3):
            eng1.step()  # crash mid-churn: chunked prefills in flight
        crashed_mid = any(r.status != "done" for r in reqs)

        eng2 = make(RequestJournal(jp))
        assert eng2.warmup() == stats0
        s2: list = []
        info = eng2.replay_pending(s2.append)
        assert crashed_mid and info["resumed"] > 0, (
            "crash happened after everything finished")
        _drain(eng2, max_steps=800)
        eng2.journal.close_clean()

        # union of both lives' client streams: every request's tokens
        # exactly once, bit-identical to `generate`
        per: dict[str, list[int]] = {}
        for evs in (s1, s2):
            for ev in evs:
                if ev.kind == "token" and ev.token is not None:
                    per.setdefault(ev.request.id, []).append(ev.token)
        for r in reqs:
            ref = np.asarray(generate(
                model, variables, jnp.asarray(r.prompt_ids)[None],
                r.max_new_tokens))[0].tolist()
            assert per.get(r.id) == ref, (
                f"{r.id}: {per.get(r.id)} != {ref}")

        # the one-executable pin: the whole gauntlet — chunk segments,
        # preemption recompute, replay — never compiled anything new
        assert eng2.compile_stats() == stats0, (
            "chunked churn recompiled the engine")
        s = eng2.metrics.summary()
        assert s["preempted"] > 0, "churn produced no preemption"
        assert RequestJournal(jp).pending_count() == 0


class TestFrontEndHardening:
    def test_malformed_line_is_a_counted_bad_request(self, tmp_path, llama):
        """Satellite: a malformed JSONL line produces a bad_request
        reject on the metrics/stream — never an engine-thread
        exception — while well-formed neighbours still complete."""
        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.serve.queue import REJECT_BAD_REQUEST
        from hyperion_tpu.serve.server import serve_jsonl

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="badline_run")
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None),
                     tracer=tracer)
        eng.warmup([8])
        lines = [
            json.dumps({"id": "ok1", "prompt_ids": list(range(2, 8)),
                        "max_new_tokens": 3}),
            "{broken json",
            json.dumps({"id": "bad_ids", "prompt_ids": "not-a-list",
                        "max_new_tokens": 3}),
            json.dumps({"id": "no_prompt"}),
        ]
        out = io.StringIO()
        summary = serve_jsonl(eng, io.StringIO("\n".join(lines) + "\n"),
                              out)
        tracer.close()
        recs = [json.loads(line) for line in out.getvalue().splitlines()]
        assert {r["id"] for r in recs if r.get("event") == "done"} == {"ok1"}
        assert sum(1 for r in recs if r.get("event") == "error") == 3
        assert summary["completed"] == 1
        snap = eng.metrics.reg.snapshot()["counters"]
        assert snap[f"serve_rejected_{REJECT_BAD_REQUEST}"] == 3
        stream = [json.loads(line) for line in
                  (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        bad = [r for r in stream if r.get("name") == "request_rejected"
               and r.get("reason") == REJECT_BAD_REQUEST]
        assert len(bad) == 3

    def test_mid_stream_disconnect_drops_sink_with_event(
            self, tmp_path, llama):
        """Satellite: a client that dies mid-stream costs its own
        request only — the sink is dropped, a client_disconnected
        event lands, the counter moves, and the engine finishes the
        slot out."""
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="dead_client")
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None),
                     tracer=tracer)
        eng.warmup([8])
        calls = {"n": 0}

        def dying_sink(ev):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise BrokenPipeError("client gone")

        req = Request(prompt_ids=_prompts([6], seed=53)[0],
                      max_new_tokens=5, id="dead", sink=dying_sink)
        healthy: list = []
        other = Request(prompt_ids=_prompts([7], seed=54)[0],
                        max_new_tokens=5, id="alive",
                        sink=healthy.append)
        eng.submit(req)
        eng.submit(other)
        _drain(eng)
        tracer.close()
        assert req.status == "done" and len(req.tokens) == 5
        assert req.sink is None  # dropped at the second write
        assert other.status == "done"
        assert eng.metrics.summary()["dropped_sinks"] == 1
        recs = [json.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        assert any(r.get("name") == "client_disconnected"
                   and r.get("request") == "dead" for r in recs)


class TestSupervisedKill:
    def test_sigkill_twice_under_supervise_bit_identical(
            self, tmp_path, llama):
        """The acceptance subprocess test: `hyperion serve --supervise`
        with two hard crashes mid-decode (`crash@tick` = `os._exit`,
        nothing flushed beyond the kernel). The supervisor restarts
        twice, the journal replays across three process lives, and the
        client's combined stdout stream carries every request's temp-0
        tokens bit-identical to an uninterrupted `generate` — each
        token exactly once, one done per request."""
        import os
        import subprocess
        import sys as sys_mod

        from hyperion_tpu.checkpoint.io import export_gathered
        from hyperion_tpu.obs.report import read_records

        model, variables = llama
        ckpt = tmp_path / "llama.npz"
        export_gathered(ckpt, variables["params"])
        jp = tmp_path / "journal.jsonl"
        tele = tmp_path / "telemetry.jsonl"
        prompts = _prompts([6, 7], seed=61)
        budgets = [12, 10]
        lines = "".join(
            json.dumps({"id": f"k{i}", "prompt_ids": p.tolist(),
                        "max_new_tokens": budgets[i]}) + "\n"
            for i, p in enumerate(prompts))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HYPERION_TELEMETRY=str(tele))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run(
            [sys_mod.executable, "-m", "hyperion_tpu.cli.main", "serve",
             "--ckpt", str(ckpt), "--no-tokenizer",
             "--max-len", "48", "--slots", "2", "--warmup-lens", "8,32",
             "--journal", str(jp),
             "--supervise", "--max-restarts", "3", "--hang-timeout", "0",
             "--chaos", "crash@tick=3,crash@tick=6"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=420, cwd=str(Path(__file__).resolve().parents[1]),
        )
        assert r.returncode == 0, r.stderr[-3000:]
        assert r.stderr.count("[serve-supervisor] child exit 70") == 2
        assert r.stdout.count("[chaos] firing crash@tick") == 2

        per_tokens: dict[str, list[int]] = {}
        dones: dict[str, int] = {}
        for line in r.stdout.splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # chaos chatter on the shared stdout
            if rec.get("event") == "token" and rec.get("token") is not None:
                per_tokens.setdefault(rec["id"], []).append(rec["token"])
            elif rec.get("event") == "done":
                dones[rec["id"]] = dones.get(rec["id"], 0) + 1
        for i, p in enumerate(prompts):
            ref = np.asarray(generate(
                model, variables, jnp.asarray(p)[None],
                budgets[i]))[0].tolist()
            assert per_tokens[f"k{i}"] == ref, (
                f"k{i}: {per_tokens[f'k{i}']} != {ref}")
            assert dones[f"k{i}"] == 1
        # the journal drained clean in the last life
        from hyperion_tpu.serve.journal import RequestJournal

        assert RequestJournal(jp).pending_count() == 0
        # the replays are visible on the stream as resumed requests
        records = read_records(tele)
        assert any(rec.get("name") == "serve_prefill" and rec.get("resumed")
                   for rec in records)
        assert any(rec.get("name") == "request_admitted"
                   and rec.get("replayed") for rec in records)


class TestLoadSoak:
    @pytest.mark.slow
    def test_soak_under_poisson_load(self, llama):
        """Longer closed-loop soak: backpressure engages (tiny queue),
        everything accounted for, no recompiles, clean drain."""
        eng = _engine(llama, slots=4, queue_capacity=6,
                      prefill_budget=48)
        spec = LoadSpec(n_requests=80, rate_hz=400.0,
                        prompt_lens=(4, 8, 16, 24), max_new=(4, 8, 16),
                        vocab=250, seed=1)
        stats0 = eng.warmup(list(spec.prompt_lens))
        report = run_load(eng, spec)
        assert report["completed"] + report["rejected"] \
            + report["timed_out"] == 80
        assert report["completed"] > 0
        assert report["tokens_per_s"] > 0
        assert eng.compile_stats() == stats0
        assert eng.idle


# -------------------------------------------------- live plane (PR 10)


class TestSLOLivePlane:
    """SLO burn-rate alerting + exposition on a LIVE engine (the
    acceptance drill): seeded overload raises exactly ONE alert that
    `obs doctor` names, the alert clears after load drops (hysteresis),
    and the exposition socket answers off the running engine — all on
    the suite's already-compiled shapes, with compile stats asserted
    flat across the whole drill."""

    def test_overload_drill_raises_once_names_it_then_clears(
            self, llama, tmp_path):
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer

        model, variables = llama
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="slo_live",
                        proc=0)
        eng = Engine(
            model, variables,
            # a micro TTFT target this host's ms-scale prefills always
            # breach, with test-scaled windows so the drill clears in
            # under a second of idling; SIX requests so the quantile
            # evidence floor (obs/slo.py QUANTILE_MIN_COUNT) is met —
            # a sparser drill would rightly never page
            EngineConfig(slots=3, max_len=48, eos_id=None,
                         slo_ttft_p99_ms=0.001,
                         slo_fast_s=0.5, slo_slow_s=1.0),
            tracer=tracer)
        eng.warmup([8, 16])
        stats0 = eng.compile_stats()
        assert eng.slo is not None
        for i, p in enumerate(_prompts([5, 9, 4, 6, 7, 8], seed=11)):
            ok, reason = eng.submit(
                Request(prompt_ids=p, max_new_tokens=4, id=f"slo{i}"))
            assert ok, reason
        _drain(eng)
        # the monitor is rate-limited (fast_s/4): the drill drains in
        # milliseconds, so tick idle until the evaluation lands — the
        # fast window still holds all six TTFTs
        t0 = time.monotonic()
        while not eng.slo.active and time.monotonic() - t0 < 5.0:
            eng.step()
            time.sleep(0.02)
        assert eng.slo.active_names() == ["ttft_p99"]
        assert eng.metrics.reg.counter("serve_alerts_raised").value == 1
        # load dropped: keep ticking idle until both windows drain and
        # the alert CLEARS — the engine's serve loop evaluates on idle
        # ticks exactly so this can happen
        t0 = time.monotonic()
        while eng.slo.active and time.monotonic() - t0 < 10.0:
            eng.step()
            time.sleep(0.05)
        assert not eng.slo.active, "alert never cleared after drain"
        reg = eng.metrics.reg
        assert reg.counter("serve_alerts_raised").value == 1
        assert reg.counter("serve_alerts_cleared").value == 1
        assert reg.gauge("serve_alerts_active").value == 0.0
        assert eng.compile_stats() == stats0  # zero new jits
        assert eng.metrics.summary()["alerts_raised"] == 1
        tracer.close()
        recs = [json.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        events = [r for r in recs if r.get("kind") == "event"]
        assert sum(r["name"] == "alert_raised" for r in events) == 1
        assert sum(r["name"] == "alert_cleared" for r in events) == 1
        (raised,) = [r for r in events if r["name"] == "alert_raised"]
        assert raised["alert"] == "ttft_p99"
        assert raised["burn_fast"] > 1.0 and raised["burn_slow"] > 1.0
        d = doctor.diagnose(tmp_path)
        assert "slo:" in d["reason"] and "ttft_p99" in d["reason"]
        (row,) = d["slo_alerts"]
        assert row["raised"] == 1 and row["cleared"] == 1
        assert row["active"] is False

    def test_heartbeat_carries_alerts_field(self, llama, tmp_path):
        from hyperion_tpu.obs.heartbeat import Heartbeat, read_heartbeat

        model, variables = llama
        hb = Heartbeat(tmp_path / "heartbeat.json", run="slo_hb",
                       every=1)
        eng = Engine(
            model, variables,
            EngineConfig(slots=3, max_len=48, eos_id=None,
                         slo_ttft_p99_ms=0.001,
                         slo_fast_s=0.5, slo_slow_s=1.0),
            heartbeat=hb)
        eng.warmup([8])
        for i, p in enumerate(_prompts([5, 4, 6, 3, 7], seed=3)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=2,
                               id=f"hb{i}"))
        _drain(eng)
        t0 = time.monotonic()
        while eng.slo is not None and not eng.slo.active \
                and time.monotonic() - t0 < 5.0:
            eng.step()          # idle ticks until the evaluation lands
            time.sleep(0.02)
        rec = read_heartbeat(tmp_path / "heartbeat.json")
        assert rec["schema"] == 1
        assert rec["alerts"] == ["ttft_p99"]  # firing at the last beat

    def test_exposition_answers_off_live_engine(self, llama, tmp_path):
        from hyperion_tpu.obs.export import (
            MetricsExporter,
            read_exposition,
        )

        eng = _engine(llama)
        eng.warmup([8])
        stats0 = eng.compile_stats()
        eng.submit(Request(prompt_ids=_prompts([5])[0],
                           max_new_tokens=3, id="exp0"))
        _drain(eng)
        sock = tmp_path / "obs.sock"
        with MetricsExporter(sock, eng.exposition):
            doc = read_exposition(sock)
        assert doc is not None and doc["role"] == "engine"
        assert doc["phase"] == "serve_idle" and doc["queue"] == 0
        assert doc["slots"] == 3 and doc["occupancy"] == 0.0
        assert doc["draining"] is False and doc["brownout"] is False
        assert doc["alerts"] == []
        assert doc["metrics"]["counters"]["serve_completed"] == 1
        w = doc["windows"]
        assert w["window_s"] == 60.0
        assert w["histograms"]["ttft_ms"]["count"] == 1
        assert w["counters"]["tokens"]["delta"] == 3.0
        assert isinstance(doc["blocks_in_use"], int)
        # answering the socket traced nothing and touched no jit cache
        assert eng.compile_stats() == stats0


# ---------------------------------- introspection plane (PR-13)


class TestIntrospection:
    """Compile ledger, host-tick profiler, memory ledger, and the
    exposition control verb on a LIVE engine — everything on the
    suite's already-compiled shapes except the one deliberately
    shape-churned engine that PAYS for its recompile to prove the
    ledger catches it."""

    def test_concurrent_pollers_race_free_and_compile_flat(
            self, llama, tmp_path):
        """Satellite (d): N threaded `obs top`-style pollers against a
        stepping engine — every answer complete and well-formed, zero
        new jit compiles from answering."""
        import threading

        from hyperion_tpu.obs import top as top_mod
        from hyperion_tpu.obs.export import MetricsExporter

        eng = _engine(llama)
        eng.warmup([8, 16])
        stats0 = eng.compile_stats()
        for i, p in enumerate(_prompts([5, 9, 4, 6], seed=21)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=6,
                               id=f"poll{i}"))
        rows: list[dict] = []
        errors: list[str] = []
        stop = threading.Event()

        def poll():
            try:
                while not stop.is_set():
                    row = top_mod.sample("process", tmp_path,
                                         timeout_s=2.0)
                    rows.append(row)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        with MetricsExporter(tmp_path / "obs.sock", eng.exposition,
                             control_fn=eng.control):
            threads = [threading.Thread(target=poll) for _ in range(4)]
            for t in threads:
                t.start()
            _drain(eng)
            for _ in range(8):      # a few idle ticks under fire too
                eng.step()
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        assert rows, "pollers never sampled"
        live = [r for r in rows if r["source"] == "socket"]
        assert live, rows[:3]
        for r in live:              # the stable row schema held under
            assert set(top_mod.ROW_KEYS) <= set(r)   # concurrency
            assert r["state"] == "live"
        # the introspection columns answer off the live payload
        assert any(r["dominant_segment"] is not None for r in live)
        assert all(isinstance(r["rss_mb"], (int, float)) for r in live)
        # answering N pollers compiled nothing and recompiled nothing
        assert eng.compile_stats() == stats0
        assert eng.ledger.recompiles == 0

    def test_exposition_carries_introspection_payload(
            self, llama, tmp_path):
        from hyperion_tpu.obs.tickprof import SEGMENTS

        eng = _engine(llama)
        eng.warmup([8])
        eng.submit(Request(prompt_ids=_prompts([5], seed=22)[0],
                           max_new_tokens=4, id="intro0"))
        _drain(eng)
        doc = eng.exposition()
        tp = doc["tickprof"]
        assert tp["ticks"] > 0 and tp["dominant"] in ("other", *SEGMENTS)
        assert tp["segments"][tp["dominant"]]["frac"] > 0
        mem = doc["memory"]
        assert mem["param_bytes"] > 0 and mem["kv_pool_bytes"] > 0
        assert mem["blocks_in_use_bytes"] == 0  # drained
        assert isinstance(mem["rss_mb"], float) and mem["rss_mb"] > 0
        comp = doc["compile"]
        assert comp["recompiles"] == 0
        assert comp["tick_executables"] >= 1
        # the warmup ledger recorded per-executable compile wall time
        led = eng.ledger.warmup
        assert led and "tick" in led["compile_s"]
        assert any(k.startswith("prefill_b") for k in led["compile_s"])

    def test_shape_churn_raises_exactly_one_recompile_incident(
            self, tmp_path):
        """The acceptance drill: a deliberately shape-churned run (a
        prompt outside the warmed bucket ladder, on a uniquely-
        dimensioned model so the process-wide caches can't mask it)
        raises exactly one `recompile_after_warmup` doctor incident
        naming the executable."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer

        model = Llama(llama_tiny_config(vocab_size=97, max_len=64))
        variables = {"params": model.init_params(jax.random.key(1),
                                                 seq=8)}
        tracer = Tracer(tmp_path / "telemetry.jsonl", run="churn")
        eng = Engine(model, variables,
                     EngineConfig(slots=2, max_len=48, eos_id=None),
                     tracer=tracer)
        eng.warmup([8])     # ladder stops at bucket 8 — deliberately
        assert eng.ledger.recompiles == 0
        # a 20-token prompt needs the UNWARMED 32 bucket (power-of-
        # two ladder): this engine pays a prefill compile post-warmup,
        # which is the invariant breach the ledger must catch
        eng.submit(Request(prompt_ids=_prompts([20], seed=23,
                                               vocab=97)[0],
                           max_new_tokens=3, id="churn0"))
        _drain(eng)
        assert eng.ledger.recompiles == 1
        assert eng.metrics.reg.snapshot()["counters"][
            "serve_recompiles"] == 1
        assert eng.metrics.summary()["recompiles"] == 1
        tracer.close()
        recs = [json.loads(line) for line in
                (tmp_path / "telemetry.jsonl").read_text().splitlines()]
        events = [r for r in recs
                  if r.get("name") == "recompile_after_warmup"]
        assert len(events) == 1, events
        assert events[0]["executable"] == "prefill_executables"
        assert events[0]["last_prefill_bucket"] == 32
        d = doctor.diagnose(tmp_path)
        assert len(d["recompile_incidents"]) == 1
        assert "recompile after warmup" in d["reason"]
        assert "prefill_executables" in d["reason"]
        assert "warmup ladder" in d["reason"]
        md = doctor.render_markdown(d)
        assert "broken invariant" in md

    def test_slow_journal_named_dominant_host_segment(
            self, llama, tmp_path):
        """A seeded slow-journal run (fault callable sleeping inside
        every append) must yield a doctor incident naming the journal
        as the dominant host segment — not a vague 'host-bound'."""
        from hyperion_tpu.obs import doctor
        from hyperion_tpu.obs.trace import Tracer
        from hyperion_tpu.serve.journal import RequestJournal

        tracer = Tracer(tmp_path / "telemetry.jsonl", run="slowj")
        journal = RequestJournal(tmp_path / "journal.jsonl",
                                 fault=lambda tag: time.sleep(0.004))
        model, variables = llama
        eng = Engine(model, variables,
                     EngineConfig(slots=3, max_len=48, eos_id=None,
                                  snapshot_every=4),
                     tracer=tracer, journal=journal)
        eng.warmup([8, 16])
        stats0 = eng.compile_stats()
        for i, p in enumerate(_prompts([5, 9, 4], seed=24)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=14,
                               id=f"slowj{i}"))
        _drain(eng)
        journal.close()
        snap = eng.tickprof.snapshot()
        assert snap["dominant"] == "journal", snap
        assert snap["ticks"] >= 8
        assert eng.compile_stats() == stats0
        tracer.close()
        d = doctor.diagnose(tmp_path)
        assert d["host_segment_incidents"], d["tickprof"]
        assert "host segment 'journal'" in d["reason"]
        assert "slow disk" in d["reason"]
        assert "host-bound" in doctor.render_markdown(d)

    def test_step_segments_are_spans_in_a_trace(self, llama, tmp_path):
        """A tiny engine stepped under the profiler leaves `serve.step`
        with its tick, a span for every segment that ran and the
        children of `device` and `admit`, each inside its parent in
        time — and compiles nothing for it."""
        from hyperion_tpu.obs import xprof
        from hyperion_tpu.obs.tickprof import SEGMENTS
        from hyperion_tpu.utils import profiling

        eng = _engine(llama)
        eng.warmup([8, 16])
        stats0 = eng.compile_stats()
        before = eng.tickprof.ticks_recorded
        with profiling.capture(tmp_path / "trace"):
            for i, p in enumerate(_prompts([5, 11], seed=26)):
                eng.submit(Request(prompt_ids=p, max_new_tokens=4,
                                   id=f"span{i}"))
            _drain(eng)
        assert eng.compile_stats() == stats0
        recs = eng.tickprof.tail(eng.tickprof.ticks_recorded - before)
        ran = {k for r in recs for k in r["s"]}
        assert {"admit", "device", "accept", "slo"} <= ran
        prof = xprof.load(xprof.xplane_path(tmp_path / "trace"))
        spans: dict[str, list] = {}
        for plane in prof.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve.step"):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
        steps = spans["serve.step"]
        assert len(steps) == len(recs)
        # one number names a step in the trace and in its record: the
        # span that holds a record's moment carries the record's tick
        assert [st["tick"] for _, _, st in sorted(steps)] == \
            [r["tick"] for r in recs]
        assert len({r["tick"] for r in recs if "device" in r["s"]}) == \
            sum("device" in r["s"] for r in recs)
        # every key a record holds is a span of that name, children too
        for key in ran:
            assert f"serve.step/{key}" in spans, key
        assert {k for k in ran if "/" not in k} <= set(SEGMENTS)
        children = {"device/dispatch", "device/fetch", "admit/gate",
                    "admit/blocks", "admit/upload", "admit/dispatch",
                    "admit/fetch"}
        assert children <= {k[len("serve.step/"):] for k in spans}

        def inside(name, parent):
            return all(any(ps <= s and e <= pe for ps, pe, _ in spans[parent])
                       for s, e, _ in spans[name])

        for child in children:
            parent = "serve.step/" + child.split("/")[0]
            assert inside("serve.step/" + child, parent), child
            assert inside(parent, "serve.step")
        # a prefill's three children say which bucket and which hit
        for name in ("upload", "dispatch", "fetch"):
            for _, _, st in spans[f"serve.step/admit/{name}"]:
                assert st["bucket"] in (8, 16) and st["start"] == 0

    def test_admit_blocks_children_sum_to_it_stage_by_stage(
            self, llama, request):
        """TestTieredKV's drill (seed a shared prefix, evict it to the
        host tier under pressure, hit it again) runs every stage of
        `_claim_blocks`: each that ran is a child of `admit/blocks`,
        one that did not opened nothing, and together they hold its
        seconds to within a tenth."""
        eng = _engine(llama, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16,
                      host_cache_mb=8)
        eng.warmup()
        rng = np.random.default_rng(83)
        shared = rng.integers(1, 250, 16).astype(np.int32)
        # a collection between two children (0.1 s in a process that
        # holds JAX) would be `admit/blocks`' own: none while timing
        gc.disable()
        request.addfinalizer(gc.enable)

        def tail(n):
            return np.concatenate(
                [shared, rng.integers(1, 250, n).astype(np.int32)])

        def run(*reqs):
            before = eng.tickprof.ticks_recorded
            for r in reqs:
                ok, reason = eng.submit(r)
                assert ok, reason
                eng.step()
            _drain(eng)
            recs = eng.tickprof.tail(eng.tickprof.ticks_recorded - before)
            stages: dict[str, float] = {}
            for r in recs:
                for k, v in r["s"].items():
                    if k.startswith("admit/blocks"):
                        stages[k] = stages.get(k, 0.0) + v
            return stages

        p0 = tail(3)
        first = run(Request(prompt_ids=p0, max_new_tokens=4, id="s0"))
        # nothing to copy and nothing to restore yet: no such child
        assert set(first) == {
            "admit/blocks", "admit/blocks/lookup",
            "admit/blocks/alloc_evict", "admit/blocks/reserve",
            "admit/blocks/account"}
        # a second prompt that leaves the first inside its second block
        forked = run(Request(prompt_ids=np.concatenate(
            [p0[:12], rng.integers(1, 250, 6).astype(np.int32)]),
            max_new_tokens=4, id="s1"))
        run(*[Request(prompt_ids=rng.integers(1, 250, 6),
                      max_new_tokens=12, id=f"gr{i}") for i in range(3)])
        assert len(eng.host) >= 2           # the chain went to the host
        again = run(Request(prompt_ids=tail(4), max_new_tokens=4, id="s2"))
        assert eng.metrics.summary()["host_restored_blocks"] >= 2
        assert "admit/blocks/restore" in again
        assert "admit/blocks/restore" not in forked
        seen = set(first) | set(forked) | set(again)
        assert seen == {
            "admit/blocks", "admit/blocks/lookup",
            "admit/blocks/alloc_evict", "admit/blocks/reserve",
            "admit/blocks/cow", "admit/blocks/restore",
            "admit/blocks/account"}
        # the two admissions that copied and restored, together
        whole = forked.pop("admit/blocks") + again.pop("admit/blocks")
        kids = sum(forked.values()) + sum(again.values())
        assert 0.9 * whole <= kids <= whole + 1e-5, (whole, forked, again)

    def test_every_fetch_names_its_arrays_and_the_flight_account_holds(
            self, llama):
        """`device/fetch` and `admit/fetch` hold one child an array in
        the order the host waits; the four flight counters are in every
        record, ordered as they must be, and the window's sums ride the
        exposition."""
        eng = _engine(llama)
        eng.warmup([8, 16])
        for i, p in enumerate(_prompts([5, 11, 7], seed=31)):
            eng.submit(Request(prompt_ids=p, max_new_tokens=4, id=f"f{i}"))
        _drain(eng)
        eng.step()                  # idle: a step that ran no tick
        recs = eng.tickprof.tail(32)
        assert "device" not in recs[-1]["s"]
        first = recs[0]["s"]
        for key in ("admit/fetch/tokens", "admit/fetch/finished",
                    "device/fetch/tokens", "device/fetch/finished",
                    "ensure", "count"):
            assert first.get(key, 0) > 0, (key, first)
        # a dense model's tick counts nothing on the device
        assert "device/fetch/counters" not in first
        for rec in recs:
            c, s = rec["c"], rec["s"]
            assert c["step_us"] == round(rec["total_s"] * 1e6)
            assert 0 <= c["fetch_after_ready_us"] <= c["inflight_us"] \
                <= c["step_us"]
            assert c["gc_us"] >= 0
            flights = sum(s.get(f"{p}/dispatch", 0) + s.get(f"{p}/fetch", 0)
                          for p in ("admit", "chunk", "device"))
            # dispatch start to fetch end, the stretch between the two
            # included
            assert c["inflight_us"] >= round(1e6 * flights) - 3
            later = sum(v for k, v in s.items()
                        if k.endswith("/fetch/finished"))
            assert c["fetch_after_ready_us"] == pytest.approx(
                1e6 * later, abs=2 + 1e-2 * 1e6 * later)
        assert recs[-1]["c"]["inflight_us"] == 0
        sums = eng.exposition()["tickprof"]["inflight"]
        assert sums["step_us"] == sum(r["c"]["step_us"] for r in recs)
        assert 0 < sums["inflight_us"] < sums["step_us"]

    def test_a_collection_inside_a_step_is_counted_once_and_balanced(
            self, llama):
        """A forced `gc.collect()` inside a step leaves `gc_us` and one
        balanced `serve.step/gc` span in that engine's step, nothing in
        a second engine's; the segment it interrupted keeps the seconds;
        a dead engine's hook leaves `gc.callbacks`."""
        import weakref

        from hyperion_tpu.obs import tickprof

        def hooks():
            return [cb for cb in gc.callbacks
                    if isinstance(cb, tickprof._CollectorWatch)]

        model, variables = llama
        cfg = EngineConfig(slots=3, max_len=48, eos_id=None)
        eng = Engine(model, variables, cfg,
                     on_event=lambda ev: gc.collect())
        other = Engine(model, variables, cfg)
        assert sum(cb._prof() in (eng.tickprof, other.tickprof)
                   for cb in hooks()) == 2
        log = []

        class _Span:
            def __init__(self, name, args):
                self.name, self.args = name, args

            def __enter__(self):
                log.append(("open", self.name, self.args))

            def __exit__(self, *exc):
                log.append(("close", self.name))

        eng.tickprof._annotate = lambda name, **args: _Span(name, args)
        eng.warmup([8])
        eng.submit(Request(prompt_ids=_prompts([5], seed=33)[0],
                           max_new_tokens=3, id="gc0"))
        _drain(eng)
        recs = eng.tickprof.tail(8)
        assert recs and all(r["c"]["gc_us"] > 0 for r in recs
                            if "device" in r["s"])
        for r in recs:
            # the collections ran inside `admit` and `accept` (the sink
            # of an emitted token): nothing was netted out of them
            # (a young collection of the interpreter's own may fall
            # anywhere in the step: a few hundred microseconds)
            held = r["s"].get("admit", 0) + r["s"].get("accept", 0)
            assert 0.9 * r["c"]["gc_us"] <= 1e6 * held
            assert r["c"]["gc_us"] <= r["c"]["step_us"]
        opened = [e for e in log if e[1] == "serve.step/gc"]
        assert opened and len(opened) % 2 == 0
        for i, e in enumerate(log):
            if e[:2] == ("open", "serve.step/gc"):
                assert e[2]["generation"] in (0, 1, 2)
                assert log[i + 1] == ("close", "serve.step/gc")
        assert ("open", "serve.step/gc", {"generation": 2}) in log
        # the other engine's hook saw every collection and no step of its
        assert other.tickprof._gc_s == 0.0
        assert other.tickprof.ticks_recorded == 0
        gone = weakref.ref(eng.tickprof)
        del eng, recs, log
        gc.collect()
        assert gone() is None
        assert all(cb._prof() is not None for cb in hooks())
        assert sum(cb._prof() is other.tickprof for cb in hooks()) == 1
        del other
        gc.collect()
        assert all(cb._prof() is not None for cb in hooks())

    def test_tick_record_keeps_its_keys_and_gains_children(self, llama):
        """The record's contract: every segment key it had, seconds
        each; children beside them, never more than their parent and
        never in `other`."""
        from hyperion_tpu.obs.tickprof import SEGMENTS

        eng = _engine(llama)
        eng.warmup([8])
        eng.submit(Request(prompt_ids=_prompts([6], seed=27)[0],
                           max_new_tokens=5, id="keys0"))
        _drain(eng)
        recs = eng.tickprof.tail(32)
        first = recs[0]["s"]        # the step that admitted and ticked
        for key in ("admit", "bt_upload", "device", "accept", "slo",
                    "admit/blocks", "admit/upload", "admit/dispatch",
                    "admit/fetch", "device/dispatch", "device/fetch"):
            assert first.get(key, 0) > 0, (key, first)
        for rec in recs:
            s = rec["s"]
            top = {k: v for k, v in s.items() if "/" not in k}
            assert set(top) <= set(SEGMENTS)
            assert sum(top.values()) <= rec["total_s"] + 1e-5
            # at every depth: `admit`'s children, `admit/blocks`' own
            for parent in {k.rpartition("/")[0] for k in s if "/" in k}:
                kids = sum(v for k, v in s.items()
                           if k.rpartition("/")[0] == parent)
                assert kids <= s.get(parent, 0.0) + 1e-5, (parent, s)
        snap = eng.tickprof.snapshot()
        assert not any("/" in k for k in snap["segments"])
        named = sum(v["s"] for k, v in snap["segments"].items()
                    if k != "other")
        assert snap["segments"].get("other", {"s": 0.0})["s"] == \
            pytest.approx(snap["total_s"] - named, abs=1e-5)
        assert "device/fetch" in snap["children"]
        # the exposition and the flight record carry both
        assert set(eng.exposition()["tickprof"]["counters"]) == {
            "kv_tokens", "prefill_tokens", "kv_blocks_walked",
            "kv_table_entries", "kv_blocks_written", "kv_rows_written",
            "prompt_positions_tiled", "prompt_positions_gather"}
        assert set(eng._flight_payload()["ticks"][-1]["c"]) == {
            "kv_tokens", "prefill_tokens", "sampling_rows",
            "restricted_rows", "kv_blocks_walked", "kv_table_entries",
            "kv_blocks_written", "kv_rows_written",
            "prompt_positions_tiled", "prompt_positions_gather",
            # the profiler's own four, in every record
            "step_us", "inflight_us", "fetch_after_ready_us", "gc_us"}
        assert set(eng.exposition()["tickprof"]["inflight"]) == {
            "step_us", "inflight_us", "fetch_after_ready_us", "gc_us"}

    def test_tick_counters_follow_the_slots(self, llama):
        """`kv_tokens` is host bookkeeping of what the live slots hold in
        the pool: a request's prompt and what it generated, less its
        newest token (sampled, written by the next tick) — through
        admission, decode, finish and preemption."""
        eng = _engine(llama, slots=3, block_size=8, num_blocks=8,
                      admission="optimistic", queue_capacity=16)
        eng.warmup()
        rng = np.random.default_rng(28)
        reqs = [Request(prompt_ids=rng.integers(1, 250, 6 + i).astype(
                            np.int32),
                        max_new_tokens=7 + 5 * (i % 3), id=f"cnt{i}")
                for i in range(7)]
        for r in reqs:
            ok, _ = eng.submit(r)
            assert ok
        steps = 0
        seen_finish = False
        while not eng.idle:
            done_before = sum(r.status == "done" for r in reqs)
            eng.step()
            steps += 1
            assert steps < 400
            rec = eng.tickprof.tail(1)[0]
            c = rec["c"]
            live = [r for r in eng._slots if r is not None]
            assert c["kv_tokens"] == sum(
                len(r.prompt_ids) + len(r.tokens) - 1 for r in live)
            assert len(live) == eng.n_active
            # padded tokens of this step's prefills: a power of two each
            admitted = sum(k.startswith("admit/dispatch") for k in rec["s"])
            assert (c["prefill_tokens"] > 0) == bool(admitted)
            seen_finish |= sum(r.status == "done"
                               for r in reqs) > done_before
        assert seen_finish and eng.metrics.summary()["preempted"] > 0
        assert eng.tickprof.tail(1)[0]["c"]["kv_tokens"] == 0

    def test_write_counters_follow_the_grain(self, llama):
        """`kv_blocks_written` / `kv_rows_written` against hand
        arithmetic: a prompt from position 0 goes in by whole blocks
        (the mapped entries its bucket covers), one that a mid-block
        prefix hit starts inside a block goes row by row (its bucket is
        whole blocks: the program's run-time branch), and a tick writes
        a row a live slot."""
        eng = _engine(llama, block_size=8)
        eng.warmup([26])
        rng = np.random.default_rng(33)
        A = rng.integers(1, 250, 24).astype(np.int32)
        B = np.concatenate([A[:20], rng.integers(1, 250, 6).astype(np.int32)])

        def counted():
            c = eng.tickprof.tail(1)[0]["c"]
            return c["kv_blocks_written"], c["kv_rows_written"]

        eng.submit(Request(prompt_ids=A, max_new_tokens=4, id="wA"))
        eng.step()
        # 24 positions from 0 in a bucket of 32: three mapped blocks of
        # 8 (the bucket's fourth is padding: dropped), then the tick's
        # one row
        assert counted() == (3, 1)
        eng.step()
        assert counted() == (0, 1)
        _drain(eng)
        eng.submit(Request(prompt_ids=B, max_new_tokens=4, id="wB"))
        eng.step()
        # the radix cache holds A's two whole blocks and forks its third
        # at the 20th token: the prefill starts at 20, inside a block;
        # its 6 positions ride a bucket of 8, all inside mapped blocks
        assert eng.metrics.summary()["cow_copies"] == 1
        assert counted() == (0, 8 + 1)
        _drain(eng)
        # the exposition sums the window's steps; the doctor says both
        snap = eng.exposition()["tickprof"]["counters"]
        ticks = sum("device" in r["s"] for r in eng.tickprof.tail(64))
        assert snap["kv_blocks_written"] == 3
        assert snap["kv_rows_written"] == 8 + ticks

    def test_profiled_run_compiles_nothing(self, llama, tmp_path):
        """The acceptance criterion: `compile_stats()` flat across a
        profiled run — bracketing jax.profiler around live ticks adds
        zero executables (and degrades to a structured answer where
        tracing is unsupported)."""
        from hyperion_tpu.utils.profiling import on_demand_trace

        eng = _engine(llama)
        eng.warmup([8])
        stats0 = eng.compile_stats()
        res = on_demand_trace(tmp_path / "prof", 0.3)
        assert res["status"] in ("started", "unsupported", "busy"), res
        eng.submit(Request(prompt_ids=_prompts([5], seed=25)[0],
                           max_new_tokens=5, id="prof0"))
        _drain(eng)
        if res["status"] == "started":
            time.sleep(0.45)    # let the daemon timer stop the trace
        assert eng.compile_stats() == stats0
        assert eng.ledger.recompiles == 0

    def test_a_trace_being_written_out_answers_busy_at_once(
            self, tmp_path, monkeypatch):
        """Stopping a trace writes it out, seconds on a busy host; a
        request that arrives meanwhile gets `busy` at once and does not
        wait for the writer (it waited on the lock, past the control
        client's 5 s: the answer the driver's loaded run never got)."""
        from hyperion_tpu.utils import profiling

        stopping, release = threading.Event(), threading.Event()

        # a list of this test's own: an earlier test's trace may still
        # be written out (over 10 s on the driver's loaded run), and its
        # thread clears the list it knows
        monkeypatch.setattr(profiling, "_TRACE_ACTIVE", [])

        def idle():
            for _ in range(200):
                if not profiling._TRACE_ACTIVE:
                    return True
                time.sleep(0.05)
            return False

        def slow_stop():
            stopping.set()
            release.wait(10.0)

        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **kw: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
        first = profiling.on_demand_trace(tmp_path / "a", 0.1)
        assert first["status"] == "started"
        assert stopping.wait(5.0)
        t0 = time.monotonic()
        second = profiling.on_demand_trace(tmp_path / "b", 0.1)
        assert second == {"status": "busy", "dir": str(tmp_path / "a")}
        assert time.monotonic() - t0 < 1.0
        release.set()
        assert idle()

    def test_profile_control_verb_answers(self, llama, tmp_path):
        """`obs profile` end to end minus the CLI: the control request
        through the exposition socket starts (or declines) a trace and
        answers a status dict, never an error envelope."""
        from hyperion_tpu.obs.export import MetricsExporter, request_control

        eng = _engine(llama)
        eng.warmup([8])
        stats0 = eng.compile_stats()
        sock = tmp_path / "obs.sock"
        with MetricsExporter(sock, eng.exposition,
                             control_fn=eng.control):
            res = request_control(
                sock, {"cmd": "profile", "seconds": 0.2,
                       "out": str(tmp_path / "prof2")})
            assert res["kind"] == "control"
            assert res["status"] in ("started", "unsupported", "busy")
            # a malformed control request answers an error dict
            bad = request_control(sock, {"cmd": "profile"})
            assert bad["status"] == "error" and "out" in bad["error"]
            unknown = request_control(sock, {"cmd": "nope"})
            assert unknown["status"] == "error"
        if res["status"] == "started":
            time.sleep(0.35)
        assert eng.compile_stats() == stats0


@pytest.mark.parametrize("family", ["llama", "ouro"])
def test_a_model_without_experts_counts_no_expert_rows(family):
    """`expert_rows_kernel` / `expert_rows_ragged` belong to a model
    whose config states an expert step (`afmoe`, `smallthinker`:
    tests/test_smallthinker.py): every other model's records, its
    exposition and its doctor row are what they were."""
    from hyperion_tpu.obs.tickprof import EXPERT_ROW_COUNTERS

    if family == "llama":
        from hyperion_tpu.models.llama import Llama, llama_tiny_config
        model = Llama(llama_tiny_config())
    else:
        from hyperion_tpu.models.ouro import Ouro, ouro_tiny_config
        model = Ouro(ouro_tiny_config())
    assert not hasattr(model.cfg, "expert_step")
    params = model.init_params(jax.random.key(0))
    eng = Engine(model, {"params": params},
                 EngineConfig(slots=3, max_len=64, block_size=4))
    eng.submit(Request(prompt_ids=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=3, id="a"))
    while not eng.idle:
        eng.step()
    recs = eng.tickprof.tail(16)
    assert recs and not any(
        k in r["c"] for r in recs for k in EXPERT_ROW_COUNTERS)
    assert not set(EXPERT_ROW_COUNTERS) & set(
        eng.exposition()["tickprof"]["counters"])
