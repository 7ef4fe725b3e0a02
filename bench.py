"""Measurement children and the one parent that runs them in sequence.

`python bench.py` prints one JSON line. The primary row is sustained
bf16 matmul TFLOPS at 8192^3 on one chip — the reference's own headline
microbenchmark (MI250X: 121.07 TFLOPS bf16 at 8192^2, `Phase 1/results/
benchmarks/hardware/precision_results.csv:13`; BASELINE.md);
`vs_baseline` is achieved/baseline. This is NOT yet the repo's
benchmark: no model configuration, no traffic mix, no per-layer
breakdown. The PR that defines `BENCHMARK.json` replaces it; until then
this file only has to be honest.

How it stays honest:

- The parent imports no JAX. Each measurement runs in a child process,
  strictly one after another, so a child that needs the chip finds it
  free (a chip belongs to one process at a time).
- The matmul child times K data-dependent matmuls inside ONE jit, fenced
  by fetching a scalar reduction of the last output; per-iteration time
  is the slope between two chain lengths. A result above the chip's
  nominal peak, a non-finite probe value, or a t(N)/t(N/2) ratio far
  from 8x marks it implausible.
- Anything wrong is a failure: a child that exits non-zero, times out or
  prints no JSON, a device that is not a TPU, an implausible reading.
  The parent then says why on stderr and EXITS NON-ZERO without a result
  line. There is no probe, no retry, no substitute measurement and no
  last-known-good number.

Rows riding the same line: `extra` (GPT-2-shaped LM train step),
`input_pipeline`, `serving`, `serving_scale`, `fleet_sim` and
`decode_attention`. The last five are host-backend probes of tiny
models (their children run under JAX_PLATFORMS=cpu and say so in their
`platform` field): they track host code across rounds and are not
device metrics.

Telemetry: opt-in via HYPERION_TELEMETRY=1 (appends to
results/benchmarks/telemetry.jsonl) or HYPERION_TELEMETRY=<path>.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

BASELINE_TFLOPS_BF16_8192 = 121.07  # MI250X bf16 8192^2 (BASELINE.md)
N = int(os.environ.get("HYPERION_BENCH_N", "8192"))  # override for smoke tests

# Canonical gate vocabulary of the decode_attention probe row: every
# name here is PROMISED to `obs diff` (scripts/check_diff_gates.py
# fails tier-1 if one is not gated in obs/diff.py METRICS, and the
# child stamps these names directly like the fleet_sim row). Kept at
# module top level — bench.py's top-level imports are jax-free, so the
# drift guard can import this without touching a backend.
DECODE_ATTN_REPORT_KEYS = (
    "decode_attn_tokens_per_s",          # pallas paged kernel (higher)
    "decode_attn_gather_tokens_per_s",   # gather reference (higher)
    "decode_attn_recompiles",            # jit growth under churn (0-pinned)
)


def _chained_matmul_tflops(n: int, k1: int, k2: int):
    """Sustained bf16 matmul TFLOPS at n^3 via a data-dependent chain."""
    import jax
    import jax.numpy as jnp

    from hyperion_tpu.utils.timing import time_chained

    k0, kb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(k0, (n, n), jnp.bfloat16)
    # Fold the unit-scale normalization into B once, outside the chain:
    # each c @ b_scaled then keeps the carry at unit variance with NO
    # per-iteration elementwise epilogue riding along with the matmul
    # (the old `(c @ b) * inv` cost a 2x128MB HBM round-trip per iter
    # at 8192^2 when XLA declined to fuse it — part of the 88.8%-MFU gap).
    b = jax.random.normal(kb, (n, n), jnp.bfloat16) * (1.0 / n ** 0.5)

    def mm(c, b):
        return c @ b

    res = time_chained(mm, a, b, k1=k1, k2=k2, n_thread=1)
    tflops = (2 * n**3 / (res.per_iter_ms / 1e3)) / 1e12
    return tflops, res


def _child_matmul() -> None:
    import math

    import jax

    from hyperion_tpu.utils.chips import device_kind, mfu, nominal_peak_tflops

    tflops, res = _chained_matmul_tflops(N, k1=16, k2=48)
    peak = nominal_peak_tflops("bfloat16")
    util = mfu(tflops, "bfloat16")

    # Scaling guard: per-iter time must scale ~N^3 between N/2 and N.
    scaling_ratio = None
    if N >= 2048:
        _, half = _chained_matmul_tflops(N // 2, k1=32, k2=96)
        if half.per_iter_ms > 0:
            scaling_ratio = res.per_iter_ms / half.per_iter_ms

    checks = {
        "probe_finite": math.isfinite(res.probe),
        "under_peak": peak is None or tflops <= 1.05 * peak,
        "n_cubed_scaling": scaling_ratio is None or 3.0 <= scaling_ratio <= 20.0,
    }
    out = {
        "tflops": round(tflops, 2),
        "per_iter_ms": round(res.per_iter_ms, 3),
        "amortized_ms": round(res.amortized_ms, 3),
        "dispatch_overhead_ms": round(res.overhead_ms, 2),
        "chain_lengths": [res.k1, res.k2],
        "peak_tflops": peak,
        "mfu": round(util, 4) if util is not None else None,
        "scaling_ratio_vs_half_n": (
            round(scaling_ratio, 2) if scaling_ratio is not None else None
        ),
        "plausible": all(checks.values()),
        "checks": checks,
        "platform": jax.devices()[0].platform,
        "device_kind": device_kind(),
    }
    print(json.dumps(out))


def _child_lm_step() -> None:
    """GPT-2-shaped LM (d768/12h/4L, seq 128) train-step throughput.

    The train step is chained by threading (params, opt_state) through
    scan — each step's gradients depend on the previous step's params,
    so the per-step time cannot be faked by a lazy fence."""
    import jax
    import jax.numpy as jnp
    import optax

    from hyperion_tpu.models.transformer_lm import TransformerLM, gpt2_lm_config
    from hyperion_tpu.train import make_optimizer, next_token_loss
    from hyperion_tpu.utils.timing import time_chained

    bsz, seq = 32, 128
    model = TransformerLM(gpt2_lm_config(dtype="bfloat16", dropout=0.0))
    params = model.init_params(jax.random.key(0), batch=2)
    opt = make_optimizer(2e-4, grad_clip_norm=1.0)
    opt_state = opt.init(params)
    ids = jax.random.randint(jax.random.key(1), (bsz, seq), 0, 50257, jnp.int32)
    mask = jnp.ones((bsz, seq), jnp.int8)

    def step(params, opt_state, ids, mask):
        def loss_fn(p):
            logits = model.apply({"params": p}, ids, padding_mask=mask)
            return next_token_loss(logits, ids, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    res = time_chained(step, params, opt_state, ids, mask,
                       k1=4, k2=12, n_thread=2)
    t = res.per_iter_ms / 1e3
    print(json.dumps({
        "lm_step_ms": round(res.per_iter_ms, 2),
        "lm_step_amortized_ms": round(res.amortized_ms, 2),
        "lm_tokens_per_s": round(bsz * seq / t, 1),
        "dispatch_overhead_ms": round(res.overhead_ms, 2),
    }))


def _child_input_pipeline() -> None:
    """Host input-pipeline probe: batches/sec of `ShardedBatches` epoch
    assembly, sync vs background-prefetched (data/prefetch.py), under a
    small fixed simulated per-batch step so the prefetch thread has
    compute to hide behind — the ratio is the fraction of host assembly
    the overlap actually removed from the critical path. Runs on the
    host backend (the parent forces JAX_PLATFORMS=cpu): the measured
    quantity is host assembly + dispatch rate; no chip involved."""
    import time

    import jax

    from hyperion_tpu.data.prefetch import Prefetcher
    from hyperion_tpu.data.sharding import ShardedBatches
    from hyperion_tpu.data.text import synthetic_lm_split
    from hyperion_tpu.runtime.mesh import MeshSpec, make_mesh

    # sized so assembly is a visible fraction of the simulated step —
    # a probe whose assembly rounds to zero can't show overlap moving
    global_batch, depth, step_s = 256, 2, 0.002
    split = synthetic_lm_split(2048, seq_len=512, seed=0)
    batches = ShardedBatches(split.arrays(), global_batch,
                             make_mesh(MeshSpec(data=-1)), seed=0)

    def rate(d: int, epochs: int = 3) -> float:
        n = 0
        t0 = time.perf_counter()
        for ep in range(epochs):
            with Prefetcher(batches.epoch(ep), depth=d) as feed:
                for b in feed:
                    jax.block_until_ready(b["input_ids"])
                    time.sleep(step_s)  # the stand-in device step
                    n += 1
        return n / (time.perf_counter() - t0)

    rate(0, epochs=1)  # warmup: first-touch allocations, thread pools
    sync = rate(0)
    prefetched = rate(depth)
    print(json.dumps({
        "sync_batches_per_s": round(sync, 2),
        "prefetch_batches_per_s": round(prefetched, 2),
        "speedup": round(prefetched / sync, 3) if sync else None,
        "global_batch": global_batch,
        "prefetch_depth": depth,
        "simulated_step_ms": step_s * 1e3,
        "seq_len": 512,
    }))


def _child_serving() -> None:
    """Serving probe: the continuous-batching engine (serve/engine.py)
    on the host backend under a seeded Poisson load (serve/loadgen.py)
    with a 64-token SHARED system prompt, reporting the user-facing
    SLOs — tokens/sec, TTFT p50/p99, reject rate — plus the paged-KV-
    cache pressure keys (prefix hit rate, prefill tokens saved, blocks
    in use, HBM per request) that `obs diff` gates like throughput.
    Chip-free like the input_pipeline probe (the parent forces
    JAX_PLATFORMS=cpu). The tiny queue capacity is deliberate: a probe that never rejects
    can't regress on backpressure, and a probe whose requests share a
    prefix can't silently lose the radix cache."""
    import jax

    from hyperion_tpu.models.llama import Llama, llama_tiny_config
    from hyperion_tpu.serve.engine import Engine, EngineConfig
    from hyperion_tpu.serve.loadgen import LoadSpec, run_load

    cfg = llama_tiny_config(max_len=128)
    model = Llama(cfg)
    params = model.init_params(jax.random.key(0), seq=8)
    engine = Engine(
        model, {"params": params},
        # SLO targets deliberately generous (host-CPU TTFTs are tens
        # of ms): a healthy round reports alerts_raised=0 and a
        # regression that tanks the windowed tail RAISES — the
        # lower-is-better key `obs diff` gates off this row
        EngineConfig(slots=4, max_len=128, eos_id=None,
                     queue_capacity=8, prefill_budget=96,
                     slo_ttft_p99_ms=10_000.0, slo_availability=0.5,
                     slo_fast_s=5.0, slo_slow_s=20.0,
                     # the probe is the one place the AOT cost pull is
                     # cheap and worth keeping on the record
                     ledger_costs=True),
    )
    shared = 64
    spec = LoadSpec(n_requests=32, rate_hz=100.0,
                    prompt_lens=(4, 8, 16), max_new=(4, 8, 12),
                    vocab=cfg.vocab_size, seed=0,
                    shared_prefix_tokens=shared)
    engine.warmup([shared + p for p in spec.prompt_lens])
    report = run_load(engine, spec)
    report["compile"] = engine.compile_stats()
    # compile ledger: per-executable warmup wall seconds (+ AOT
    # FLOPs/bytes) ride the row so a compile-time regression is diffable
    # like a throughput one; `recompiles` (post-warmup growth) comes via
    # run_load and is gated at zero
    led = engine.ledger.warmup or {}
    report["compile_s"] = led.get("compile_s") or {}
    report["compile_total_s"] = led.get("total_s")
    if led.get("costs"):
        report["compile_costs"] = led["costs"]

    # ---- the @spec dimension: speculative decoding off vs k∈{2,4} on
    # a longer-decode cut of the SAME seeded shared-prefix workload
    # (speculation pays on decode ticks; the base row's 4-12 token
    # budgets are prefill-dominated, so the sweep stretches max_new to
    # where the tick count actually lives). Fresh engine per point —
    # the jit caches are process-wide, so each extra point costs one
    # spec-tick compile, nothing else. accept_rate/tokens_per_tick
    # from the k=4 point ride the row top-level for `obs diff`
    # (higher-is-better); the off point pins the sequential baseline
    # (tokens_per_tick == 1.0 by construction).
    spec_load = LoadSpec(n_requests=16, rate_hz=100.0,
                         prompt_lens=(4, 8, 16), max_new=(24, 32, 48),
                         vocab=cfg.vocab_size, seed=0,
                         shared_prefix_tokens=shared)
    report["spec"] = {}
    for label, k in (("off", 0), ("k2", 2), ("k4", 4)):
        eng = Engine(
            model, {"params": params},
            EngineConfig(slots=4, max_len=128, eos_id=None,
                         queue_capacity=8, prefill_budget=96,
                         spec_k=k, draft="ngram" if k else "off"),
        )
        eng.warmup([shared + p for p in spec_load.prompt_lens])
        r = run_load(eng, spec_load)
        report["spec"][label] = {
            key: r.get(key)
            for key in ("tokens_per_s", "tokens_per_tick", "accept_rate",
                        "spec_drafted", "spec_accepted", "spec_rejected",
                        "ttft_p99_ms", "e2e_p99_ms", "completed")
        }
        if label == "k4":
            report["accept_rate"] = r.get("accept_rate")
            report["tokens_per_tick"] = r.get("tokens_per_tick")

    # ---- the @class dimension: the workload-isolation drill as a
    # bench point — the SAME seeded shared-prefix workload with every
    # 3rd request class=batch and one hostile long-prompt batch tenant
    # riding along, chunked prefill on, the class-aware brownout armed.
    # The verdict keys (interactive TTFT p99 while under attack, batch
    # shed rate) ride the row TOP-LEVEL: they are what
    # `serve_interactive_ttft_p99_ms` / `serve_batch_shed_rate` gate,
    # measured where the hostile tenant actually runs.
    cls_load = LoadSpec(n_requests=24, rate_hz=100.0,
                        prompt_lens=(4, 8, 16), max_new=(4, 8, 12),
                        vocab=cfg.vocab_size, seed=0,
                        shared_prefix_tokens=shared,
                        batch_every=3,
                        adversary="oversize", adversary_every=6,
                        adversary_prompt_len=96)
    eng = Engine(
        model, {"params": params},
        EngineConfig(slots=4, max_len=128, eos_id=None,
                     queue_capacity=8, prefill_budget=96,
                     prefill_chunk=32,
                     brownout=True, brownout_depth=6,
                     batch_deadline_s=5.0),
    )
    eng.warmup([shared + p for p in cls_load.prompt_lens])
    r = run_load(eng, cls_load)
    report["class"] = {
        key: r.get(key)
        for key in ("tokens_per_s", "completed", "shed",
                    "brownout_clamped", "recompiles", "ttft_p99_ms",
                    *(f"{cls}_{k}" for cls in ("interactive", "batch")
                      for k in ("ttft_p99_ms", "tpot_p99_ms",
                                "completed", "shed", "shed_rate")))
    }
    report["class"]["compile"] = eng.compile_stats()
    for key in ("interactive_ttft_p99_ms", "batch_shed_rate",
                "interactive_shed", "batch_shed"):
        report[key] = r.get(key)

    # ---- the @rehit dimension: the tiered-KV drill as a bench point —
    # the SAME seeded shared-prefix workload with a middle churn of
    # distinct long prompts sized to evict the shared chain from a
    # deliberately small device pool, run host tier OFF (the re-hit
    # re-prefills from scratch) and ON (the re-hit restores evicted
    # blocks from host RAM). The ON point's tier keys ride the row
    # TOP-LEVEL: they are what `serve_tier_hit_rate_host` /
    # `serve_restore_bytes_per_s` gate, measured where eviction
    # actually happens; the OFF point pins the re-prefill baseline the
    # `serve_prefill_tokens_saved` delta is judged against.
    rehit_load = LoadSpec(n_requests=24, rate_hz=100.0,
                          prompt_lens=(4, 8, 16), max_new=(4, 8, 12),
                          vocab=cfg.vocab_size, seed=0,
                          shared_prefix_tokens=shared,
                          rehit_churn=8)
    report["rehit"] = {}
    for label, mb in (("off", 0), ("host", 8)):
        eng = Engine(
            model, {"params": params},
            EngineConfig(slots=4, max_len=128, eos_id=None,
                         queue_capacity=8, prefill_budget=96,
                         num_blocks=48, host_cache_mb=mb),
        )
        eng.warmup([shared + p for p in rehit_load.prompt_lens])
        r = run_load(eng, rehit_load)
        report["rehit"][label] = {
            key: r.get(key)
            for key in ("tokens_per_s", "completed", "prefix_hit_rate",
                        "prefill_tokens_saved", "tier_hits_device",
                        "tier_hits_host", "tier_miss",
                        "tier_hit_rate_host", "restore_bytes_per_s",
                        "host_cache_mb", "recompiles")
        }
        if label == "host":
            for key in ("tier_hits_device", "tier_hits_host",
                        "tier_miss", "tier_hit_rate_host",
                        "restore_bytes_per_s", "host_cache_mb"):
                report[key] = r.get(key)
    print(json.dumps(report))


def _child_serving_scale() -> None:
    """Replica-scaling probe: the SAME seeded socket workload driven
    through `hyperion route` at 1 replica and again at N=2, on the
    host backend over the real wire path (router socket -> dispatch ->
    replica sockets). Reports aggregate serve_tokens_per_s at each
    width, the scaleup ratio, per-replica request share (fairness =
    min share x N; 1.0 = perfectly even), and the affinity hit rate —
    the router-layer numbers `obs diff` gates so a dispatch-policy
    regression can't hide behind healthy single-engine rows. Chip-free
    like the serving probe; subprocess replicas compile the tiny model
    each, so this is the slowest probe and runs last."""
    import tempfile
    import time as time_mod
    from pathlib import Path

    import jax

    from hyperion_tpu.checkpoint.io import export_gathered
    from hyperion_tpu.models.llama import Llama, llama_tiny_config
    from hyperion_tpu.serve.loadgen import LoadSpec, run_load_socket

    work = Path(tempfile.mkdtemp(prefix="serving_scale_"))
    cfg = llama_tiny_config(max_len=128)
    export_gathered(work / "llama.npz",
                    Llama(cfg).init_params(jax.random.key(0), seq=8))
    shared = 48
    spec = LoadSpec(n_requests=16, rate_hz=40.0, prompt_lens=(4, 8, 16),
                    max_new=(4, 8), vocab=cfg.vocab_size, seed=0,
                    shared_prefix_tokens=shared)

    def fleet(n: int) -> tuple[dict, dict]:
        base = work / f"fleet_{n}"
        sock = str(work / f"route_{n}.sock")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("HYPERION_TELEMETRY", None)  # router stream defaults
        proc = subprocess.Popen(
            [sys.executable, "-m", "hyperion_tpu.cli.main", "route",
             "--replicas", str(n), "--min-ready", str(n),
             "--ckpt", str(work / "llama.npz"),
             "--no-tokenizer", "--base-dir", str(base),
             "--socket", sock, "--max-len", "128", "--slots", "2",
             "--warmup-lens", f"8,{shared + 16}",
             "--queue-capacity", "16",
             "--replica-heartbeat-every", "1",
             # generous per-replica SLO targets (like the serving
             # probe's): healthy rounds tally fleet_alerts_raised=0,
             # a tail regression raises — keeps the row's
             # alerts_raised key live instead of structurally zero
             "--slo-ttft-p99-ms", "10000", "--slo-availability", "0.5",
             "--slo-fast-s", "5", "--slo-slow-s", "20"],
            env=env, stderr=subprocess.DEVNULL)
        try:
            t0 = time_mod.monotonic()
            while not Path(sock).exists():
                if proc.poll() is not None or \
                        time_mod.monotonic() - t0 > 240:
                    raise RuntimeError(f"router ({n} replicas) never "
                                       "came up")
                time_mod.sleep(0.2)
            rep = run_load_socket(sock, spec, session_every=4)
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
        end = {}
        tele = base / "telemetry.jsonl"
        if tele.exists():
            for line in tele.read_text().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("name") == "router_end":
                    end = rec
        return rep, end

    rep1, _ = fleet(1)
    n = 2
    repn, endn = fleet(n)
    share = endn.get("per_replica_dispatched") or {}
    total = sum(share.values()) or 1
    shares = {k: round(v / total, 4) for k, v in sorted(share.items())}
    fairness = round(min(shares.values()) * len(shares), 4) \
        if len(shares) == n else 0.0
    tps1 = rep1.get("tokens_per_s") or 0.0
    tpsn = repn.get("tokens_per_s") or 0.0
    print(json.dumps({
        "replicas": n,
        "requests": spec.n_requests,
        "completed_1r": rep1.get("completed"),
        "completed": repn.get("completed"),
        "tokens_per_s_1r": tps1,
        "tokens_per_s": tpsn,
        "scaleup": round(tpsn / tps1, 3) if tps1 else None,
        "ttft_p50_ms": repn.get("ttft_p50_ms"),
        "ttft_p99_ms": repn.get("ttft_p99_ms"),
        # live-plane keys: the client-side windowed tail plus the
        # fleet alert tally the router counted off replica heartbeats
        "ttft_p99_windowed_ms": repn.get("ttft_p99_windowed_ms"),
        "alerts_raised": endn.get("fleet_alerts_raised", 0),
        "request_share": shares,
        "fairness": fairness,
        "affinity_hit_rate": endn.get("affinity_hit_rate"),
        "redispatched": endn.get("redispatched"),
        "ejections": endn.get("ejections"),
        # exactly-once audit from the CLIENT side of the fleet run:
        # stream-indexed duplicate deliveries (obs diff zero-pins it)
        "duplicate_tokens": repn.get("duplicate_tokens", 0),
        # cross-process tracing keys (obs diff gates both): router
        # overhead as the CLIENT measured it (its TTFT minus the
        # replica-attributed ttft_ms on the done record), and the p99
        # failover gap off the router's own histogram (0.0 on a round
        # with no failover — the gate stays live either way)
        "router_overhead_p99_ms": repn.get("router_overhead_p99_ms"),
        "failover_gap_p99_ms": endn.get("failover_gap_p99_ms", 0.0),
    }))


def _child_fleet_sim() -> None:
    """Fleet flight-simulator probe (serve/simulate.py): the pinned
    `herd` and `failover` scenarios replayed on the discrete-event
    harness — the REAL router dispatch/steering/brownout/failover
    policy over hundreds of virtual replicas, no jits, seconds of
    wall clock. Reports the DIFF_GATED subset under canonical
    sim_<scenario>_<key> names so `obs diff` gates policy regressions
    (a worse herd completion rate, a longer failover gap, ANY
    duplicate delivery) the same way it gates engine throughput.
    Chip-free by construction, so the row rides success AND failure
    lines."""
    import tempfile
    from pathlib import Path

    from hyperion_tpu.serve.simulate import (DIFF_GATED, diff_key,
                                             run_scenario)

    work = Path(tempfile.mkdtemp(prefix="fleet_sim_"))
    row: dict = {}
    for name in sorted(DIFF_GATED):
        res = run_scenario(name, out=str(work / name))
        rep = res["report"]
        for key in DIFF_GATED[name]:
            row[diff_key(name, key)] = rep.get(key)
        row[f"sim_{name}_ok"] = bool(res["ok"])
        row[f"sim_{name}_wall_s"] = res["wall_s"]
    print(json.dumps(row))


def _child_decode_attention() -> None:
    """Paged decode-attention probe: the gather path vs the Pallas
    block-table-walk kernel (ops/pallas/paged_attention) at a pinned
    (slots, MB, block_size) decode geometry, with block tables and
    base depths CHURNING across timed calls — the serve engine's
    steady state, and the retrace trap a naive kernel falls into.
    Reports throughput for both paths plus the jit-cache growth across
    the churn (`decode_attn_recompiles`, zero-pinned: table contents
    are runtime data, one executable must serve them all). Chip-free
    (the parent forces JAX_PLATFORMS=cpu, where the kernel is
    interpreted). NOTE: on the
    host backend the kernel runs under the Pallas INTERPRETER, so
    `decode_attn_speedup` < 1 is expected and informational — the
    gather/pallas numbers are each gated against their own history,
    never against each other."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperion_tpu.models.llama import (_chain_view,
                                           _grouped_cache_attention)
    from hyperion_tpu.ops.pallas.paged_attention import (KERNEL_REV,
                                                         paged_attention)

    # pinned geometry: 4 slots, 1-token decode, GQA rep 2, 8x16 tables
    S, T, H, Hkv, D = 4, 1, 4, 2, 64
    bs, MB = 16, 8
    rep, L = H // Hkv, MB * bs
    NB = S * MB + 1  # pool incl. the null block
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (S, T, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, Hkv, bs, D), jnp.float32)

    @jax.jit
    def gather(q, kp, vp, bt, base):
        # the llama.py gather read, verbatim shape-for-shape
        kv_pos = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1)
        q_pos = base[:, None, None] + \
            jax.lax.broadcasted_iota(jnp.int32, (T, L), 0)[None]
        return _grouped_cache_attention(
            q, _chain_view(kp, bt), _chain_view(vp, bt),
            kv_pos[None] <= q_pos, rep)

    pallas = jax.jit(paged_attention)

    def tables(seed: int):
        rng = np.random.default_rng(seed)
        bt = np.zeros((S, MB), np.int32)
        base = rng.integers(bs, L - T, S).astype(np.int32)
        for b in range(S):
            nmapped = (int(base[b]) + T + bs - 1) // bs
            bt[b, :nmapped] = rng.permutation(np.arange(1, NB))[:nmapped]
        return jnp.asarray(bt), jnp.asarray(base)

    variants = [tables(i) for i in range(8)]
    bt0, base0 = variants[0]
    ref = jax.block_until_ready(gather(q, kp, vp, bt0, base0))
    out = jax.block_until_ready(pallas(q, kp, vp, bt0, base0))
    err = float(jnp.max(jnp.abs(ref - out)))
    warm = pallas._cache_size()

    def rate(fn, iters: int = 24) -> float:
        t0 = time.perf_counter()
        for i in range(iters):
            bt, base = variants[i % len(variants)]
            jax.block_until_ready(fn(q, kp, vp, bt, base))
        return S * T * iters / (time.perf_counter() - t0)

    g = rate(gather)
    p = rate(pallas)
    print(json.dumps({
        "decode_attn_tokens_per_s": round(p, 1),
        "decode_attn_gather_tokens_per_s": round(g, 1),
        "decode_attn_recompiles": int(pallas._cache_size() - warm),
        "decode_attn_speedup": round(p / g, 3) if g else None,
        "decode_attn_max_abs_err": err,
        "kernel_rev": KERNEL_REV,
        "interpret": jax.default_backend() != "tpu",
        "platform": jax.default_backend(),
        "geometry": {"slots": S, "window": T, "mb": MB, "block_size": bs,
                     "heads": H, "kv_heads": Hkv, "head_dim": D},
    }))


# (row in the result line, child flag, timeout in seconds, needs the chip)
CHILDREN = (
    ("measurement", "--child-matmul", 600, True),
    ("extra", "--child-lm-step", 420, True),
    ("input_pipeline", "--child-input-pipeline", 180, False),
    ("fleet_sim", "--child-fleet-sim", 120, False),
    ("decode_attention", "--child-decode-attention", 120, False),
    ("serving", "--child-serving", 180, False),
    ("serving_scale", "--child-serving-scale", 420, False),
)
_CHILD_FNS = {
    "--child-matmul": _child_matmul,
    "--child-lm-step": _child_lm_step,
    "--child-input-pipeline": _child_input_pipeline,
    "--child-serving": _child_serving,
    "--child-serving-scale": _child_serving_scale,
    "--child-fleet-sim": _child_fleet_sim,
    "--child-decode-attention": _child_decode_attention,
}


class ChildFailed(RuntimeError):
    pass


def _run_child(mode: str, timeout_s: int, env: dict | None = None) -> dict:
    """Run one measurement child to its end; its last JSON line, or
    ChildFailed. The child inherits the environment as it is — the
    compile cache is placed by `utils/compile_cache.py` inside it."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, **(env or {})},
        )
    except subprocess.TimeoutExpired:
        raise ChildFailed(f"{mode} timed out after {timeout_s}s") from None
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-3:]
        raise ChildFailed(
            f"{mode} exited rc={proc.returncode}: " + " | ".join(tail))
    for line in reversed((proc.stdout or "").strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise ChildFailed(f"{mode} produced no JSON output")


def main() -> int:
    import time

    # proc=0 is passed explicitly so the tracer never imports the
    # jax-loading dist module in this parent — children own all jax work
    from hyperion_tpu.obs import heartbeat as obs_heartbeat
    from hyperion_tpu.obs import trace as obs_trace

    tracer = obs_trace.from_env(
        "results/benchmarks/telemetry.jsonl",
        run=f"bench_n{N}_{int(time.time())}", proc=0,
    )
    hb = obs_heartbeat.Heartbeat.for_tracer(tracer)
    metric = f"matmul_bf16_{N}_tflops"  # baseline only comparable at N=8192
    tracer.event("bench_start", metric=metric)
    rows: dict[str, dict] = {}
    try:
        for row, mode, timeout_s, on_chip in CHILDREN:
            hb.pulse(phase=row, timeout_s=timeout_s)
            rows[row] = _run_child(
                mode, timeout_s,
                env=None if on_chip else {"JAX_PLATFORMS": "cpu"})
            tracer.event("measure_result", row=row, ok=True)
            if row == "measurement":
                m = rows[row]
                if m.get("platform") != "tpu":
                    raise ChildFailed(
                        f"the device is {m.get('platform')!r} "
                        f"({m.get('device_kind')}), not a TPU")
                if not m.get("plausible"):
                    raise ChildFailed(
                        f"guard rejected the measurement "
                        f"({m.get('checks')}): raw {m.get('tflops')} TFLOPS")
    except ChildFailed as e:
        tracer.event("publish", value=0.0, failed=True, error=str(e))
        hb.close(phase="done", value=0.0)
        tracer.close()
        print(f"bench.py: {e}", file=sys.stderr)
        return 1
    primary = rows.pop("measurement")
    out = {
        "metric": metric,
        "value": primary["tflops"],
        "unit": "TFLOPS",
        "vs_baseline": (
            round(primary["tflops"] / BASELINE_TFLOPS_BF16_8192, 3)
            if N == 8192 else 0.0
        ),
        "mfu": primary.get("mfu"),
        "platform": primary["platform"],
        "device_kind": primary.get("device_kind"),
        "measurement": primary,
        **rows,
    }
    if N != 8192:
        out["note"] = f"smoke run at N={N}; vs_baseline only defined at N=8192"
    tracer.event("publish", value=out["value"], vs_baseline=out["vs_baseline"])
    hb.close(phase="done", value=out["value"])
    tracer.close()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1:
        from hyperion_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        _CHILD_FNS[sys.argv[1]]()
    else:
        sys.exit(main())
