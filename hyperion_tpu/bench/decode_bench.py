"""Decode benchmark: prefill latency + per-token generation throughput.

Beyond the reference's benchmark surface (it never samples — SURVEY
§2): measures the KV-cache decode path `infer.generate` uses, per model
size. The decode step threads (cache, token, index) through
`utils.timing.time_chained` — each step's cache update and argmax feed
the next step, so the measurement is data-dependent end to end and the
lazy-fence failure mode round 2 exposed cannot touch it. Prefill is a
single host-fenced forward.

CLI: `python -m hyperion_tpu.bench.decode_bench [--models tiny mid]
[--batch 8] [--prompt-len 128] [--out dir]`.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from hyperion_tpu.bench.util import write_csv
from hyperion_tpu.models.llama import Llama, init_cache, llama_tiny_config
from hyperion_tpu.utils.memory import (
    compiled_peak_bytes,
    live_bytes_in_use,
    peak_bytes_in_use,
)
from hyperion_tpu.utils.timing import time_chained, time_fn

# "mid" ≈ a 1B-shaped model: big enough that decode is HBM-bound like
# production decoding, small enough to init on one chip quickly.
# "7b" is the Llama-2-7B geometry (models/llama.py llama_7b_config;
# reference distributed_utils.py:465-467) at a 1k context so the bf16
# weights (13.5 GB) + KV cache fit next to decode buffers in 16 GB —
# the VERDICT r4 item-8 speculative pairing target.
MODEL_SPECS = {
    "tiny": dict(max_len=512),
    "mid": dict(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=16, ff_dim=5504, max_len=2048, dtype="bfloat16",
    ),
    "7b": dict(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=32, ff_dim=11008, max_len=1024, dtype="bfloat16",
    ),
}


def _init_model(name: str, **overrides):
    """overrides: e.g. vocab_size, so a draft model can share the
    target's vocab (speculation verifies token ids — mismatched vocabs
    cannot pair)."""
    cfg = llama_tiny_config(**{**MODEL_SPECS[name], **overrides})
    model = Llama(cfg)
    params = jax.jit(
        lambda r: model.init_params(r, seq=min(8, cfg.max_len))
    )(jax.random.key(0))
    return cfg, model, params


def _prefill_and_chain(cfg, model, variables, ids, decode_len: int):
    """One prefill jit + the chained one-token decode measurement —
    the shared core of benchmark_decode and the breakeven analysis
    (ONE copy of the cache-budget guard and chain setup).

    Returns (t_prefill, t_chain) timing results."""
    batch = ids.shape[0]
    prompt_len = ids.shape[1]
    if prompt_len + decode_len > cfg.max_len:
        raise ValueError(
            f"{prompt_len + decode_len} tokens > max_len {cfg.max_len}"
        )
    # weights ride as jit ARGUMENTS, not closure captures: captured
    # params are baked into the program as constants (a 3.76 GB
    # constants warning and multi-minute compiles on the mid/gpt2
    # models — how the round-4 decode stage blew its time limit)
    prefill = jax.jit(
        lambda v, i: model.apply(
            v, i, cache=init_cache(cfg, batch), cache_index=0,
        )
    )
    t_prefill = time_fn(prefill, variables, ids, warmup=2, iters=5)
    logits, cache = prefill(variables, ids)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

    def decode_step(cache, tok, idx, v):
        logits, cache = model.apply(
            v, tok[:, None], cache=cache, cache_index=idx
        )
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        return cache, nxt, idx + 1

    budget = cfg.max_len - prompt_len - 1  # longest legal chain
    if budget < 2:
        raise ValueError(
            f"prompt_len {prompt_len} leaves a {budget}-step decode "
            f"budget in max_len {cfg.max_len} — shorten the prompt"
        )
    # decode_len sets the measured chain; auto-growth (fast models under
    # timer resolution) may extend it, but never past the context
    k2 = max(2, min(decode_len, budget))
    k1 = max(1, min(k2 - 1, k2 // 3))
    t = time_chained(
        decode_step, cache, tok0, jnp.int32(prompt_len), variables,
        k1=k1, k2=k2, n_thread=3, max_k2=budget,
    )
    # static peak of ONE decode step (params + cache + step buffers) —
    # what the CPU test backend, which has no allocator counters, reports
    step_peak = compiled_peak_bytes(
        jax.jit(decode_step), cache, tok0, jnp.int32(prompt_len), variables
    )
    return t_prefill, t, step_peak


def benchmark_decode(
    name: str, batch: int = 8, prompt_len: int = 128, decode_len: int = 64,
    quant: str = "none", **overrides,
) -> dict:
    cfg, model, params = _init_model(name, **overrides)
    if quant == "int8":
        # weight-only int8 (precision/quant.py): kernels become int8 +
        # per-channel scales — half bf16's weight HBM traffic, which is
        # the bound in decode; the int8 x int8 matmuls run on the MXU
        from hyperion_tpu.precision.quant import quantize_llama

        model, params = quantize_llama(params, cfg)
        cfg = model.cfg
    variables = {"params": params}
    ids = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (batch, prompt_len)),
        jnp.int32,
    )
    t_prefill, t, step_peak = _prefill_and_chain(
        cfg, model, variables, ids, decode_len
    )
    # Memory, per phase. The PJRT allocator exposes no peak reset, so a
    # true decode-only peak is unmeasurable — instead report what IS
    # measurable honestly: live residency right after the decode chain
    # (params + KV cache + step buffers = the steady-state decode
    # footprint; per-step transients are one [B,1,V] logit row) and the
    # lifetime peak, explicitly labeled as covering init+prefill too.
    # The reference conflated exactly these (memory_allocated vs peak —
    # SURVEY §6 caveats).
    decode_live_mb = live_bytes_in_use() / 1e6
    peak_mb = peak_bytes_in_use() / 1e6
    mem_source = "allocator"
    if not peak_mb:
        # only on the CPU test backend (a TPU without allocator stats
        # raises in utils/memory.py): XLA's static analysis of the
        # compiled decode step — params + cache + step buffers
        peak_mb = step_peak / 1e6
        decode_live_mb = peak_mb
        mem_source = "xla_memory_analysis"
    return {
        "model": name,
        "mode": "chain",  # dispatch-free chained slope (see module doc)
        "quant": quant,
        "batch": batch,
        "prompt_len": prompt_len,
        "prefill_ms": round(t_prefill.median_ms, 3),
        "decode_ms_per_token": round(t.per_iter_ms, 4),
        "decode_tokens_per_s": round(t.throughput(batch), 1),
        "dispatch_overhead_ms": round(t.overhead_ms, 2),
        "decode_live_mb": round(decode_live_mb, 2),
        "lifetime_peak_mb": round(peak_mb, 2),
        "mem_source": mem_source,
        "params_m": round(
            sum(x.size for x in jax.tree.leaves(params)) / 1e6, 1
        ),
    }


# draft window shared by benchmark_speculative and the breakeven
# analysis — one constant so the JSON verdict is always computed for
# the same k as the measured gen1_spec rows beside it
SPEC_K = 4


def spec_breakeven_acceptance(
    draft_ms: float, target_ms: float, k: int = SPEC_K
) -> float:
    """Per-token draft/target agreement probability above which k-token
    speculation beats plain greedy decode (the analysis VERDICT r4
    item 8 asks for, computed from measured per-forward times).

    Plain emits 1 token per `target_ms`. A speculative round costs
    `k * draft_ms + target_ms` and emits E[tokens] =
    (1 - p^(k+1)) / (1 - p) for per-token acceptance p (the standard
    geometric acceptance model from the speculative-sampling papers).
    Breakeven is the p where E[tokens] / round_cost equals
    1 / target_ms, found by bisection (E is monotone in p). Returns
    >1.0 when even total acceptance cannot pay for the drafts — the
    honest 'speculation cannot win here' verdict."""
    cost_ratio = (k * draft_ms + target_ms) / target_ms

    def expected_tokens(p: float) -> float:
        if p >= 1.0:
            return float(k + 1)
        return (1.0 - p ** (k + 1)) / (1.0 - p)

    if expected_tokens(1.0) <= cost_ratio:
        # even perfect agreement at best TIES (==) or loses (<):
        # "beats plain decode" is unattainable
        return float("inf")
    lo, hi = 0.0, 1.0
    for _ in range(60):
        mid = (lo + hi) / 2
        if expected_tokens(mid) < cost_ratio:
            lo = mid
        else:
            hi = mid
    return round(hi, 4)


def benchmark_speculative(
    name: str, prompt_len: int = 128, decode_len: int = 64, k: int = SPEC_K,
    draft: str | None = None,
) -> tuple[list[dict], dict | None]:
    """Batch-1 whole-generation wall time: plain greedy vs speculative
    with the target as its own draft (total acceptance). The pair bounds
    the speculation machinery: `spec_ceiling` is the best case (every
    round emits k+1 tokens for one target pass, including all scheme
    overheads — draft passes, verify window, acceptance bookkeeping);
    real drafts land between the two rows depending on agreement rate.
    Both rows compile the FULL generation into one jit, so — unlike the
    `mode=chain` rows — decode_ms_per_token here INCLUDES prefill and
    one per-call dispatch, amortized over decode_len. Compare gen1 rows
    only with other gen1 rows.

    draft: name of a SMALLER model to pair as a real cross-model draft
    (VERDICT r4 item 8 — e.g. tiny drafting for 7b). Both are random-
    init, so greedy agreement — and therefore the acceptance rate — is
    adversarially bad (~chance); the row measures the machinery's real
    wall time at that floor. Together with the ceiling row it brackets
    any trained draft/target pair; the breakeven acceptance rate falls
    out of (draft_ms, target_ms, k) and lands in the results write-up."""
    from hyperion_tpu.infer.generate import generate
    from hyperion_tpu.infer.speculative import generate_speculative

    cfg, model, params = _init_model(name)
    variables = {"params": params}
    ids = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (1, prompt_len)),
        jnp.int32,
    )
    plain = jax.jit(lambda v, i: generate(model, v, i, decode_len))
    spec = jax.jit(lambda v, i: generate_speculative(
        model, v, model, v, i, decode_len, k=k))
    variants = [("gen1_plain", plain, variables),
                ("gen1_spec_ceiling", spec, variables)]
    pair = None  # (draft cfg/model/vars) when the pairing built
    if draft:
        try:
            # force the draft onto the TARGET's vocab: speculation
            # verifies token ids, so mismatched vocabs cannot pair
            # (the stock "tiny" spec carries a 256-token test vocab)
            dcfg, dmodel, dparams = _init_model(
                draft, vocab_size=cfg.vocab_size
            )
            dvars = {"params": dparams}
            # generate_speculative signature: TARGET first, draft second
            spec_draft = jax.jit(lambda v, i: generate_speculative(
                model, v, dmodel, dvars, i, decode_len, k=k))
            variants.append(
                (f"gen1_spec_draft_{draft}", spec_draft, variables)
            )
            pair = (dcfg, dmodel, dvars)
        except Exception as e:  # noqa: BLE001 — a draft-init failure
            # must not cost the plain/ceiling rows already queued
            print(f"[decode_bench] draft {draft} setup failed: "
                  f"{str(e).splitlines()[0][:120]}")
    rows = []
    for mode, fn, v in variants:
        try:
            t = time_fn(fn, v, ids, warmup=1, iters=3)
        except Exception as e:  # noqa: BLE001 — one variant's OOM must
            # not discard the rows already measured this call
            print(f"[decode_bench] {name}/{mode} failed: "
                  f"{str(e).splitlines()[0][:120]}")
            continue
        peak_mb = peak_bytes_in_use() / 1e6
        live_mb = live_bytes_in_use() / 1e6
        mem_source = "allocator"
        if not peak_mb:
            peak_mb = compiled_peak_bytes(fn, v, ids) / 1e6
            live_mb = peak_mb
            mem_source = "xla_memory_analysis"
        rows.append({
            "model": name, "mode": mode, "quant": "none", "batch": 1,
            "prompt_len": prompt_len,
            "prefill_ms": float("nan"),
            "decode_ms_per_token": round(t.median_ms / decode_len, 4),
            "decode_tokens_per_s": round(decode_len / (t.median_ms / 1e3), 1),
            "dispatch_overhead_ms": float("nan"),
            "decode_live_mb": round(live_mb, 2),
            "lifetime_peak_mb": round(peak_mb, 2),
            "mem_source": mem_source,
            "params_m": round(
                sum(x.size for x in jax.tree.leaves(params)) / 1e6, 1),
        })
        print(f"[decode_bench] {json.dumps(rows[-1])}")

    analysis = None
    if pair is not None:
        # Breakeven verdict from measured batch-1 PER-FORWARD times
        # (the gen1 rows amortize prefill+dispatch, which the cost
        # model must not include). Reuses the ALREADY-initialized
        # models — a second 13.5 GB 7B init here cost a capture stage
        # its time budget once.
        try:
            dcfg, dmodel, dvars = pair
            chain_len = min(24, decode_len)  # short chain: a slope, not a run
            _, tt, _ = _prefill_and_chain(
                cfg, model, variables, ids, chain_len)
            _, td, _ = _prefill_and_chain(
                dcfg, dmodel, dvars, ids, chain_len)
            t_target, t_draft = tt.per_iter_ms, td.per_iter_ms
            be = spec_breakeven_acceptance(t_draft, t_target, k=k)
            analysis = {
                "target": name, "draft": draft, "k": k,
                "target_fwd_ms": round(t_target, 4),
                "draft_fwd_ms": round(t_draft, 4),
                # inf = even total acceptance cannot pay for the
                # drafts (kept JSON-strict as a string verdict)
                "breakeven_acceptance": (
                    be if be != float("inf") else "unachievable"),
            }
            print(f"[decode_bench] breakeven {json.dumps(analysis)}")
        except Exception as e:  # noqa: BLE001 — analysis is a bonus;
            # never cost the measured rows
            print(f"[decode_bench] breakeven analysis failed: "
                  f"{str(e).splitlines()[0][:120]}")
    return rows, analysis


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--models", nargs="*", default=["tiny", "mid"],
                   choices=sorted(MODEL_SPECS))
    p.add_argument("--quant", nargs="*", default=["none", "int8"],
                   choices=["none", "int8"],
                   help="weight variants per model (int8 = weight-only "
                        "quantized decode, precision/quant.py)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--decode-len", type=int, default=64)
    p.add_argument("--speculative", action="store_true",
                   help="add batch-1 plain vs speculative-ceiling rows "
                        "(whole-generation jit; separate compiles, so "
                        "opt-in)")
    p.add_argument("--spec-draft", default=None,
                   choices=sorted(MODEL_SPECS),
                   help="also measure a real cross-model draft pairing "
                        "(this model drafts for each --models target)")
    p.add_argument("--no-chain", action="store_true",
                   help="skip the chained per-token rows (e.g. a "
                        "speculative-only capture stage)")
    p.add_argument("--out", default="results/benchmarks/decode")
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    out = Path(args.out)
    rows = []

    def flush() -> None:
        # incremental: rows measured before a capture-stage SIGTERM stay
        write_csv(out / "decode_benchmarks.csv", rows)

    for name in args.models:
        for quant in ([] if args.no_chain else args.quant):
            try:
                r = benchmark_decode(
                    name, args.batch, args.prompt_len, args.decode_len,
                    quant=quant,
                )
            except Exception as e:  # one model's OOM must not kill the sweep
                msg = str(e).splitlines()[0] if str(e) else repr(e)
                print(f"[decode_bench] {name}/{quant} failed: {msg}")
                continue
            rows.append(r)
            flush()
            print(f"[decode_bench] {json.dumps(r)}")
        if args.speculative:
            try:
                spec_rows, analysis = benchmark_speculative(
                    name, args.prompt_len, args.decode_len,
                    draft=args.spec_draft)
                rows.extend(spec_rows)
                flush()
                if analysis is not None:
                    out.mkdir(parents=True, exist_ok=True)
                    # keyed by target AND draft: neither other targets
                    # nor a different draft pairing may clobber this
                    (out / f"spec_breakeven_{name}_{args.spec_draft}"
                     ".json").write_text(json.dumps(analysis, indent=2))
            except Exception as e:  # noqa: BLE001 — per-variant tolerance
                msg = str(e).splitlines()[0] if str(e) else repr(e)
                print(f"[decode_bench] {name}/speculative failed: {msg}")
    if rows:
        print(f"[decode_bench] results in {out}/")


if __name__ == "__main__":
    main()
