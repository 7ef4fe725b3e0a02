"""Autoregressive generation — greedy / temperature / top-k / top-p.

Beyond reference parity: the MI250X project trains models but never
samples from them (no generation code anywhere — SURVEY §2). Here a
trained checkpoint becomes a usable text generator, built the TPU way:

  * **KV-cache decode** (`generate`) for models whose `__call__` takes
    `cache`/`cache_index` (Llama — `models/llama.py:init_cache`): one
    prefill pass writes the prompt's K/V into static [B, max_len, H, D]
    buffers, then a `lax.scan` emits one token per tick. Every shape is
    static; per-step attention is one [1, max_len] masked row — O(T)
    per token.
  * **Recompute decode** (`generate_recompute`) for any causal LM
    (TransformerLM, MoELM): the fixed-width token buffer is re-run
    through the full forward each step and the logit at the current
    position is sampled. O(T²) overall but zero model changes — causal
    attention makes future buffer positions (zeros) invisible to the
    positions that matter.

Both paths stop rows that emit `eos_id` (subsequent positions get
`pad_id`) and are deterministic at temperature 0 (argmax).

CLI: `python -m hyperion_tpu.infer.generate --prompt "..." ...` loads
the in-tree BPE tokenizer plus a gathered-export `.npz` checkpoint
(`checkpoint/io.py:export_gathered`, written by every trainer) and
prints the completion — model shape is inferred from the checkpoint.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _mask_top_k(logits: jax.Array, top_k: int) -> jax.Array:
    """Batch-uniform top-k restriction (static k): everything below the
    k-th largest logit per row becomes -inf."""
    kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _mask_top_p(logits: jax.Array, top_p) -> jax.Array:
    """Nucleus restriction: keep the smallest prefix of the sorted
    distribution whose mass reaches top_p (the first token always
    survives: its preceding cumulative mass is 0 < top_p). `top_p` may
    be a python float (batch-uniform) or a [B, 1] array (per-row —
    the serve engine's per-slot sampling params); p = 1.0 rows are an
    exact no-op: every kept value scatters back unchanged."""
    order = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    kept = jnp.where(mass_before < top_p, sorted_logits, -jnp.inf)
    # scatter back through the permutation already in hand (a second
    # argsort would re-sort the full vocab every decode tick)
    return jnp.full_like(logits, -jnp.inf).at[
        jnp.arange(logits.shape[0])[:, None], order
    ].set(kept)


def sample_token(logits: jax.Array, rng: jax.Array | None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0) -> jax.Array:
    """logits [B, V] → token ids [B]. temperature 0 = greedy; top_k and
    top_p (nucleus) restrict the support and compose (k first, then p),
    both applied after the temperature rescale."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        raise ValueError("temperature > 0 sampling needs an rng key")
    logits = logits / temperature
    if top_k > 0:
        logits = _mask_top_k(logits, top_k)
    if top_p < 1.0:
        if top_p <= 0.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        logits = _mask_top_p(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sampling_tier(temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> jax.Array:
    """The most sampling work any row asks for, an int32 scalar:
    0 = every row is greedy (temperature <= 0); 1 = some row samples and
    none of those restricts its support; 2 = some sampling row has
    top_k > 0 or top_p < 1. A greedy row's top_k / top_p are never read,
    so they cannot lift the tier."""
    samples = temperature > 0
    restricts = samples & ((top_k > 0) | (top_p < 1.0))
    return (samples.any().astype(jnp.int32)
            + restricts.any().astype(jnp.int32))


def sample_token_slots(
    logits: jax.Array,
    keys: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    live: jax.Array | None = None,
) -> jax.Array:
    """Per-row sampling for the serve engine: logits [S, V] with
    per-slot params (each [S]) → token ids [S], one fully vectorized
    call per decode tick — no per-slot python dispatch, no recompile
    when the mix of sampling params changes across slot refills.

    Row semantics match `sample_token` applied per row: temperature
    <= 0 rows are greedy (argmax — their key is never consumed, so the
    temp-0 oracle vs `generate` holds bit-exactly); positive rows
    rescale, restrict support by that row's top_k (0 = off; dynamic per
    row, so the k-th threshold comes from a full sort rather than
    `lax.top_k`) then top_p (1.0 = an exact no-op), and draw with that
    row's key. `keys` is a [S] typed PRNG key array.

    The work follows what the rows ask for, in three tiers
    (`sampling_tier`) that one `lax.switch` picks ON THE DEVICE, inside
    the one executable, so the mix may change from tick to tick with no
    recompile: 0 = the argmax alone; 1 = rescale and one categorical
    draw, no sort; 2 = the full path (two sorts of [S, V], softmax,
    cumsum, the un-sort scatter). Every tier yields each row's
    `sample_token` tokens; a tick pays tier 2 for all S rows as soon as
    one row restricts. On a v5e at [48, 32000] tier 2 costs 25.5 ms a
    tick, tier 1 under 0.1 ms and tier 0 0.006 ms (PERF.md section 6,
    PR 25; SERVING.md "Sampling tiers"). `live` ([S] bool) marks the
    rows whose result is read: a row that is not live (a freed slot
    keeps its last request's parameters) is treated as greedy and
    cannot lift the tier. The index depends on the per-slot parameters alone, never on
    `logits` or `keys`: under a `vmap` over those (the speculative
    tick's window positions) it stays unbatched and the switch stays a
    conditional instead of a select that would run every branch."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = temperature.astype(logits.dtype)
    if live is not None:
        t = jnp.where(live, t, 0)

    def draw():
        scaled = logits / jnp.where(t > 0, t, 1.0)[:, None]
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(t > 0, sampled.astype(jnp.int32), greedy)

    def restrict_and_draw():
        scaled = logits / jnp.where(t > 0, t, 1.0)[:, None]
        # per-row top-k: threshold = the clip(k-1)-th value of the row
        # sorted descending, applied only where k > 0
        k = jnp.clip(top_k, 0, V)
        sorted_desc = -jnp.sort(-scaled, axis=-1)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1
        )
        restricted = jnp.where(
            (k > 0)[:, None] & (scaled < kth), -jnp.inf, scaled
        )
        restricted = _mask_top_p(restricted, top_p[:, None])
        sampled = jax.vmap(jax.random.categorical)(keys, restricted)
        return jnp.where(t > 0, sampled.astype(jnp.int32), greedy)

    return jax.lax.switch(
        sampling_tier(t, top_k, top_p),
        (lambda: greedy, draw, restrict_and_draw),
    )


def _cfg_attr(cfg, name: str):
    """Config field lookup that sees through MoELMConfig's nesting
    (`cfg.name`, else `cfg.base.name`)."""
    val = getattr(cfg, name, None)
    if val is None:
        val = getattr(getattr(cfg, "base", cfg), name, None)
    return val


def _step_rngs(rng, n, temperature=0.0):
    if rng is None:
        if temperature > 0.0:
            # honoring sample_token's contract here, where the substitute
            # key would be made: a constant key(0) would silently sample
            # the same trajectory on every call
            raise ValueError("temperature > 0 sampling needs an rng key")
        rng = jax.random.key(0)  # greedy path: keys are never consumed
    return jax.random.split(rng, n)


def generate(
    model: Any,
    variables: dict,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    eos_id: int | None = None,
    pad_id: int = 0,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """KV-cache decoding → generated ids [B, max_new_tokens].

    `prompt_ids` [B, P] must be dense (left-to-right, no padding);
    P + max_new_tokens must fit the model's max_len."""
    from hyperion_tpu.models.llama import init_cache

    B, P = prompt_ids.shape
    cfg = model.cfg
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt {P} + {max_new_tokens} new tokens exceeds "
            f"max_len {cfg.max_len}"
        )
    # size the cache to the tokens actually produced — a cfg.max_len
    # buffer would cost max_len/(P+new) times the memory and per-step
    # attention FLOPs for nothing (positions are global either way)
    cache = init_cache(cfg, B, max_len=P + max_new_tokens)
    logits, cache = model.apply(
        variables, prompt_ids, cache=cache, cache_index=0
    )
    rngs = _step_rngs(rng, max_new_tokens, temperature)
    first = sample_token(logits[:, -1], rngs[0], temperature, top_k, top_p)
    done = jnp.zeros((B,), bool) if eos_id is None else first == eos_id

    def tick(carry, rng_t):
        cache, tok, idx, done = carry
        logits, cache = model.apply(
            variables, tok[:, None], cache=cache, cache_index=idx
        )
        nxt = sample_token(logits[:, 0], rng_t, temperature, top_k, top_p)
        nxt = jnp.where(done, pad_id, nxt)
        if eos_id is not None:
            done = done | (nxt == eos_id)
        return (cache, nxt, idx + 1, done), nxt

    if max_new_tokens == 1:
        return first[:, None]
    (_, _, _, _), rest = jax.lax.scan(
        tick, (cache, first, jnp.int32(P), done), rngs[1:]
    )
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def generate_recompute(
    model: Any,
    variables: dict,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    eos_id: int | None = None,
    pad_id: int = 0,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Cache-free decoding for any causal LM (same contract as
    `generate`): re-runs the full forward over a fixed-width buffer each
    step. Causality makes the zero future positions invisible."""
    B, P = prompt_ids.shape
    width = P + max_new_tokens
    max_len = _cfg_attr(model.cfg, "max_len")
    if width > max_len:
        raise ValueError(f"{width} tokens exceeds max_len {max_len}")
    buf = jnp.zeros((B, width), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt_ids.astype(jnp.int32), (0, 0))
    rngs = _step_rngs(rng, max_new_tokens, temperature)

    def tick(carry, rng_t):
        buf, idx, done = carry
        out = model.apply(variables, buf)
        logits = out[0] if isinstance(out, tuple) else out  # MoE aux path
        last = jax.vmap(lambda row, i: row[i])(logits, idx - 1)  # [B, V]
        nxt = sample_token(last, rng_t, temperature, top_k, top_p)
        nxt = jnp.where(done, pad_id, nxt)
        if eos_id is not None:
            done = done | (nxt == eos_id)
        buf = jax.vmap(lambda row, i, t: row.at[i].set(t))(
            buf, idx, nxt
        )
        return (buf, idx + 1, done), nxt

    done = jnp.zeros((B,), bool)
    (_, _, _), toks = jax.lax.scan(
        tick, (buf, jnp.full((B,), P, jnp.int32), done), rngs
    )
    return toks.T


# ---------------------------------------------------------------- CLI


def _infer_lm_from_npz(params: dict):
    """Rebuild a TransformerLM whose shape matches a gathered export."""
    from hyperion_tpu.models.transformer_lm import TransformerLM, simple_lm_config

    vocab, d_model = params["tok_emb"]["embedding"].shape
    max_len = params["pos_emb"]["embedding"].shape[0]
    n_layers = len([k for k in params if k.startswith("block_")])
    ff_dim = params["block_0"]["fc1"]["kernel"].shape[1]
    n_heads = params["block_0"]["attn"]["q_proj"]["kernel"].shape[1]
    cfg = simple_lm_config(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        ff_dim=ff_dim, n_heads=n_heads, max_len=max_len, dropout=0.0,
    )
    return TransformerLM(cfg)


def _infer_llama_from_npz(params: dict, max_len: int):
    """Rebuild a Llama whose shape matches a gathered export (max_len is
    not recoverable from weights — RoPE has no table — so it is a CLI
    knob)."""
    from hyperion_tpu.models.llama import Llama, LlamaConfig

    vocab, d_model = params["embed_tokens"]["embedding"].shape
    n_layers = len([k for k in params if k.startswith("layer_")])
    l0 = params["layer_0"]
    _, n_heads, _ = l0["attn"]["q_proj"]["kernel"].shape
    _, n_kv_heads, _ = l0["attn"]["k_proj"]["kernel"].shape
    ff_dim = l0["mlp"]["gate_proj"]["kernel"].shape[1]
    cfg = LlamaConfig(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_kv_heads, ff_dim=ff_dim,
        max_len=max_len, remat=False,
    )
    return Llama(cfg)


def _infer_moe_from_npz(params: dict, moe_top_k: int):
    """Rebuild an MoELM from a gathered export. Architecture comes from
    the weights (expert bank shapes, the dense/sparse block pattern);
    routing top_k is NOT in the weights — it's a CLI knob that must
    match training for outputs to match the trained router's regime."""
    from hyperion_tpu.models.moe_lm import MoELM, MoELMConfig
    from hyperion_tpu.models.transformer_lm import simple_lm_config
    from hyperion_tpu.ops.moe import MoEConfig

    vocab, d_model = params["tok_emb"]["embedding"].shape
    max_len = params["pos_emb"]["embedding"].shape[0]
    moe_idx = sorted(
        int(k.split("_")[-1]) for k in params if k.startswith("moe_block_")
    )
    dense_idx = [int(k.split("_")[-1]) for k in params
                 if k.startswith("block_")]
    n_layers = len(moe_idx) + len(dense_idx)
    # blocks (i+1) % moe_every == 0 are sparse: the first sparse index
    # recovers the cadence (all-MoE → first index 0 → every 1)
    moe_every = moe_idx[0] + 1
    bank = params[f"moe_block_{moe_idx[0]}"]["experts"]
    E, _, moe_ff = bank["wi"].shape
    first = params[f"block_{dense_idx[0]}"] if dense_idx \
        else params[f"moe_block_{moe_idx[0]}"]
    n_heads = first["attn"]["q_proj"]["kernel"].shape[1]
    ff_dim = (params[f"block_{dense_idx[0]}"]["fc1"]["kernel"].shape[1]
              if dense_idx else moe_ff)
    if not 1 <= moe_top_k <= E:
        raise ValueError(
            f"--moe-top-k {moe_top_k} out of range for this export's "
            f"{E} experts (need 1..{E}, matching training)"
        )
    base = simple_lm_config(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        ff_dim=ff_dim, n_heads=n_heads, max_len=max_len, dropout=0.0,
    )
    # the trainer wires moe.activation = base.activation (trainer.py);
    # neither is recoverable from weights, so both ride the same default
    moe = MoEConfig(n_experts=E, top_k=moe_top_k, d_model=d_model,
                    ff_dim=moe_ff, activation=base.activation)
    return MoELM(MoELMConfig(base=base, moe=moe, moe_every=moe_every))


def model_from_npz(params: dict, max_len: int = 4096, moe_top_k: int = 2):
    """(model, cached: bool) for a gathered export — Llama exports get
    the KV-cache decode path; TransformerLM and MoELM exports the
    recompute one. Pipeline exports are rejected with a clear message
    rather than rebuilt wrong."""
    if "embed_tokens" in params:
        return _infer_llama_from_npz(params, max_len), True
    if "stages" in params:
        raise ValueError(
            "pipeline checkpoints are not supported by the generation "
            "CLI — export a dense TransformerLM, MoELM, or Llama "
            "checkpoint"
        )
    if any(k.startswith("moe_block_") for k in params):
        return _infer_moe_from_npz(params, moe_top_k), False
    if "tok_emb" not in params:
        raise ValueError(
            f"unrecognized checkpoint layout (top-level keys: "
            f"{sorted(params)[:6]}...)"
        )
    return _infer_lm_from_npz(params), False


def main(argv=None) -> int:
    import argparse

    from hyperion_tpu.checkpoint.io import load_gathered
    from hyperion_tpu.data.bpe import ByteBPE

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--prompt", required=True)
    p.add_argument("--ckpt", default="data/checkpoints/language_ddp_final.npz",
                   help="gathered-export .npz (written by the trainers)")
    p.add_argument("--tokenizer-dir", default="data/tokenizer")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling: keep the smallest prefix of "
                        "the distribution reaching this mass (1.0 = off)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-len", type=int, default=4096,
                   help="context length for Llama exports (RoPE has no "
                        "weight table to infer it from)")
    p.add_argument("--quant", choices=["none", "int8"], default="none",
                   help="int8 = weight-only quantized decode "
                        "(precision/quant.py)")
    p.add_argument("--draft-ckpt", default=None,
                   help="speculative decoding: a smaller Llama export "
                        "whose proposals the main model verifies (greedy "
                        "only; same vocab; infer/speculative.py)")
    p.add_argument("--draft-k", type=int, default=4,
                   help="speculative proposals per verify round")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="MoE exports: routing top_k (not recoverable "
                        "from weights; must match training)")
    args = p.parse_args(argv)

    # decode telemetry (opt-in: HYPERION_TELEMETRY=1 or =<path>): load/
    # compile/decode spans + a tokens/sec gauge, same stream format as
    # the trainers — `hyperion_tpu obs summarize` reads it directly.
    import time

    from hyperion_tpu.obs import MetricsRegistry, observe_step, observe_throughput
    from hyperion_tpu.obs import heartbeat as obs_heartbeat
    from hyperion_tpu.obs import trace as obs_trace

    # timestamped run id: the stream file is append-only, so each CLI
    # invocation must stay separable under `obs summarize --run`
    tracer = obs_trace.from_env(
        "data/telemetry.jsonl", run=f"generate_{int(time.time())}"
    )
    # flight recorder (rides the tracer): a decode hung in compile is
    # distinguishable from one emitting tokens slowly
    hb = obs_heartbeat.Heartbeat.for_tracer(tracer)
    hb.pulse(phase="load")
    reg = MetricsRegistry()

    with tracer.span("load") as ld:
        tok = ByteBPE.load(args.tokenizer_dir)
        params = load_gathered(args.ckpt)
        model, cached = model_from_npz(params, args.max_len, args.moe_top_k)
        ld.set(ckpt=args.ckpt, cached=cached)
    if args.quant == "int8":
        from hyperion_tpu.models.transformer_lm import TransformerLMConfig
        from hyperion_tpu.precision.quant import quantize_llama, quantize_lm

        if not cached and not isinstance(model.cfg, TransformerLMConfig):
            raise SystemExit(
                "--quant int8 supports Llama and TransformerLM exports "
                "(MoE expert banks are einsum weights, not dense kernels)"
            )
        quantize = quantize_llama if cached else quantize_lm
        model, params = quantize(params, model.cfg)
    if args.draft_ckpt:
        if not cached:
            raise SystemExit("--draft-ckpt needs a Llama (KV-cache) target")
        if args.temperature > 0:
            raise SystemExit(
                "speculative decoding is greedy-only; drop --temperature"
            )
        from hyperion_tpu.infer.speculative import generate_speculative

        draft_params = load_gathered(args.draft_ckpt)
        draft_model, draft_cached = model_from_npz(draft_params, args.max_len)
        if not draft_cached:
            raise SystemExit("--draft-ckpt must be a Llama export")
        if args.quant == "int8":
            from hyperion_tpu.precision.quant import quantize_llama

            draft_model, draft_params = quantize_llama(
                draft_params, draft_model.cfg
            )
        if args.draft_k < 1:
            raise SystemExit("--draft-k must be >= 1")
        n_prompt = len(tok.encode(args.prompt))
        if n_prompt <= args.draft_k:
            raise SystemExit(
                f"prompt encodes to {n_prompt} tokens but speculative "
                f"decoding needs more than --draft-k={args.draft_k} — "
                "use a longer prompt or a smaller k"
            )
    # one jit around the WHOLE generation: prefill + the token scan (or
    # the full speculative while-loop) compile into a single XLA
    # program, so the CLI pays one dispatch instead of one per op
    if args.draft_ckpt:
        decode = jax.jit(
            lambda variables, ids, rng: generate_speculative(
                model, variables, draft_model, {"params": draft_params},
                ids, args.max_new_tokens, k=args.draft_k,
                eos_id=tok.eos_id, pad_id=tok.eos_id,
            )
        )
    else:
        _d = generate if cached else generate_recompute
        decode = jax.jit(
            lambda variables, ids, rng: _d(
                model, variables, ids, args.max_new_tokens,
                eos_id=tok.eos_id, pad_id=tok.eos_id,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, rng=rng,
            )
        )
    model_vocab = _cfg_attr(model.cfg, "vocab_size")
    if model_vocab and tok.vocab_size > model_vocab:
        print(
            f"[generate] warning: tokenizer vocab {tok.vocab_size} exceeds "
            f"model vocab {model_vocab} — prompt ids above the "
            "model's range would be silently clamped by the embedding "
            "lookup; retrain the tokenizer at or below the model vocab"
        )
    ids = jnp.asarray([tok.encode(args.prompt)], jnp.int32)
    # The whole generation is ONE compiled program (prefill + token
    # scan), so the finest honest span is the full decode call: per-token
    # "steps" inside a lax.scan have no host boundary to time. The span
    # fences on a host fetch of the output ids — the same wait the CLI
    # pays anyway to print — so dur is device-honest, and tokens/sec is
    # emitted as the decode-throughput gauge. The first call's span
    # includes compile; `decode_step` spans time each jit call.
    hb.pulse(phase="decode", tokens_requested=args.max_new_tokens)
    with tracer.span("decode_step", step=0) as sp:
        out = decode({"params": params}, ids, jax.random.key(args.seed))
        out_host = np.asarray(out)  # device->host fetch = the fence
        n_new = int(out_host.shape[-1]) * int(out_host.shape[0])
        sp.set(tokens=n_new)  # before exit: attrs land in the record
    dur = max(sp.dur_s, 1e-9)
    observe_step(reg, dur, tokens=n_new)
    observe_throughput(reg, dur, 1, tokens=n_new)  # fenced: fetch above
    tracer.snapshot(reg)
    tracer.event("generate_done", tokens=n_new,
                 tokens_per_s=reg.gauge("tokens_per_s").value)
    hb.close(phase="done", tokens=n_new)
    tracer.close()
    text = tok.decode([t for t in out_host[0] if t != tok.eos_id])
    print(args.prompt + text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
