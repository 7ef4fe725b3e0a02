"""Drives `hyperion_tpu.serve.engine.Engine` over `models/afmoe.py`
(the `trinity-large-l5-ep8` configuration) as `adapters/serve.py` drives
it over `models/llama.py`: the same loop (`_drive`, imported), the same
end-to-end arithmetic (`stats`), the same trace reduction, the same
series names for the readers. `adapters/serve.py` builds `Llama`, checks
against `reference/decoder.py` and counts a dense decoder's bytes by
name, and may not be edited, so this file brings:

  * `model_config` and `afmoe_weights` (`weights.decoder_weights`, then
    `expert_bias` zero in float32: that one draws every non-norm leaf);
  * `run`, copied from `adapters/serve.py`'s and changed where the
    cell differs: `kv_fill` is BYTES held over bytes of both pools (the
    two layer kinds cost different bytes a position), `prefill_s` is of
    the requests whose first token fell in the window, the tick records'
    counters are handed to the readers (`ctx["counted"]`), `tick_bytes`
    comes from `costs_afmoe`, and the check below;
  * `correct`: cell 1's rule (nothing compiled in the window, nothing
    lost, every finished request whole, served tokens near the
    reference's best logit: `agrees`), the tokens of the
    `shortest` finished requests AND of the shortest finished request
    whose context passed `long_over` positions, each teacher-forced
    alone through `reference/afmoe.py` (the long one is where a
    windowed layer has let blocks go and reads a slice of its table).

The new model is imported at the top of `run`: on a checkout without
it the cell fails at once."""

from __future__ import annotations

import gc

import numpy as np

from benchmarks import costs_afmoe, spec, stats
from benchmarks.adapters.serve import CLOCK, _drive, _ms, _peak_bytes

# How far below its row's best reference logit a served token lies, in
# standard deviations of the checked rows (`z`): cell 1's quantity. Cell
# 1 holds the WORST token to 0.6. That cannot decide here: where the
# bf16 hidden state moves one of a token's four picks among 256 experts
# across a near-tie, that token's logits move by up to a std (seen on
# the rows a replay reproduces: PERF.md section 6), so the system's
# worst token reads 0.16-1.00 over 41 runs and weights rounded to fp8
# 0.79-1.07 over nine: they overlap. Three terms, each between two
# readings (my chip runs, PR 26; PERF.md section 6):
#   the mean over the 390-1360 checked tokens: the system 0.0018-0.0072
#     (35 runs); every matrix rounded to fp8 0.034-0.051, `route_scale`
#     left out 0.042-0.066 (nine seeds each). What moves every logit a
#     little;
#   the share of tokens more than `TAIL_AT` below: the system 0-1.01 %,
#     fp8 2.86-6.09 %, no `route_scale` 3.66-7.51 %. A limit of 1.8 % is
#     7 tokens of 390 where the system has 1.7 on average;
#   the worst token: the system at most 1.00; a token drawn at random
#     lies about 4 below (the largest of 25024 normals), under 2 once in
#     50. What the other two cannot see: a few wrong tokens among hundreds.
MEAN_SLACK = 0.013
TAIL_AT, TAIL_SHARE = 0.3, 0.018
WORST_SLACK = 2.0


def agrees(z) -> bool:
    """Whether served tokens `z` below their rows' best reference logits
    are the reference's own, to the three limits above."""
    return bool(z.mean() <= MEAN_SLACK and (z > TAIL_AT).mean() <= TAIL_SHARE
                and z.max() <= WORST_SLACK)


def model_config(m: dict):
    from hyperion_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ff_dim=m["intermediate_size"],
        moe_ff_dim=m["moe_intermediate_size"],
        layer_types=tuple(m["layer_types"][i] for i in m["layers_kept"]),
        n_dense_layers=m["num_dense_layers"], n_experts=m["router_experts"],
        experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        n_shared_experts=m["num_shared_experts"],
        route_norm=m["route_norm"], route_scale=m["route_scale"],
        sliding_window=m["sliding_window"],
        max_len=m["max_position_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], mup_enabled=m["mup_enabled"],
        dtype=m["torch_dtype"])


def afmoe_weights(model, seed: int):
    """`weights.decoder_weights` (normal(0.02) matrices in the served
    type, norm scales 1, from the seed on the device), with every
    `expert_bias` zero in float32 as the model's own initialiser has
    it: `decoder_weights` draws every leaf that is not a norm scale."""
    import jax
    import jax.numpy as jnp

    from benchmarks.weights import decoder_weights

    return jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.zeros(w.shape, jnp.float32)
        if path[-1].key == "expert_bias" else w,
        decoder_weights(model, seed))


class _Counting:
    """The engine as `_drive` sees it, keeping each step's tick record:
    `_drive` copies the segments only, the readers here need the
    counters (`c`) too."""

    def __init__(self, engine):
        self._engine = engine
        self.records: list[tuple[float, dict]] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self):
        out = self._engine.step()
        self.records.append((CLOCK(), self._engine.tickprof.tail(1)[0]))
        return out


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.models.afmoe import Afmoe
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    m = cell["model"]
    model = Afmoe(model_config(m))
    t = CLOCK()
    params = jax.block_until_ready(afmoe_weights(model, seed))
    say(weights_s=CLOCK() - t, weight_bytes=sum(
        x.nbytes for x in jax.tree.leaves(params)))

    token_times: dict[str, list[float]] = {}
    lost: dict[str, str] = {}

    def on_event(ev):
        if ev.kind == "token":
            token_times[ev.request.id].append(CLOCK())
        else:
            lost[ev.request.id] = ev.kind

    engine = Engine(model, {"params": params},
                    EngineConfig(**cell["engine"]), on_event=on_event)
    t = CLOCK()
    engine.warmup([cell["traffic"]["prompt"]["max"]])
    compiled = engine.compile_stats()
    memory = engine.memory_ledger()
    say(warmup_s=CLOCK() - t, executables=compiled,
        kv_by_kind=memory["kv_by_kind"])

    slots = engine.cfg.slots
    sizes = costs_afmoe.afmoe_sizes(m)
    kv_token_bytes = sizes["kv_bytes_per_token"]
    pool_bytes = memory["kv_pool_bytes"]
    # the windowed pool's bound in positions: what reserve admission's
    # worst case holds, slots x (window + a chunk + two blocks)
    window_bound = memory["kv_by_kind"]["window"]["pool_bytes"] \
        // kv_token_bytes["window"] - engine.cfg.block_size
    counting = _Counting(engine)
    source = spec.plugin("traffic", cell["generator"]).Source(
        cell["traffic"], seed, m["vocab_size"])
    data = _drive(counting, source, seconds, cell, trace, trace_dir,
                  token_times, lost)
    w0, w1 = data["window"]
    no_compile = engine.compile_stats() == compiled
    peak_bytes = _peak_bytes()
    del engine, counting._engine    # the pools go; the reference needs the room
    gc.collect()

    reqs = data["requests"]
    window = data["ticks"]
    due = [r for r in reqs if w0 <= r["due"] < w1]
    done = [r for r in reqs if r["finished"]]
    gaps = [g for r in reqs for g in stats.gaps_ending_in(r["times"], w0, w1)]
    n_tokens = sum(stats.tokens_in(r["times"], w0, w1) for r in reqs)
    measured = {
        "out_tok_per_s": n_tokens / seconds,
        "gap_p99_ms": _ms(stats.percentile(gaps, 99)),
        "setup_s": w0 - t_start,
    }
    done_in_window = sum(w0 <= r["times"][-1] <= w1 for r in done)

    def held_bytes(c: dict) -> float:
        return (c["kv_tokens"] * kv_token_bytes["full"]
                + c["kv_tokens_window"] * kv_token_bytes["window"])

    # the engine's own tick records: the window's, then the traced ones
    in_window = [r["c"] for t, r in counting.records if w0 <= t <= w1]
    counted = [{**r["c"], "expert_layers": sizes["expert_layers"],
                "experts_held_all_layers":
                    sizes["expert_layers"] * sizes["experts_held"]}
               for t, r in counting.records
               if w0 <= t <= w1 and "device" in r["s"]]
    traced = [r["c"] for t, r in counting.records
              if t > w1 and "device" in r["s"]
              and "experts_touched" in r["c"]]
    in_win = [k for k in window if "device" in k["s"]]
    series = {
        "gap_s": gaps,
        # of the requests whose first token fell in the window: one sent
        # in the window waits a residence time (about 30 s) for a slot and
        # is answered after it, so cell 1's "due in the window" is empty
        "prefill_s": [r["prefill_s"] for r in reqs
                      if r["first_token"] is not None
                      and w0 <= r["first_token"] <= w1],
        "tick_device_s": [k["s"]["device"] for k in in_win],
        "tick_host_s": [k["total_s"] - k["s"]["device"]
                        - k["s"].get("admit", 0.0) - k["s"].get("chunk", 0.0)
                        for k in in_win],
        "occupancy": [k["active"] / slots for k in window],
        # bytes the running requests hold over the bytes of both pools
        "kv_fill": [held_bytes(c) / pool_bytes for c in in_window],
    }
    held = [held_bytes(c) for c in in_window] or [0.0]
    window_peak = max((c["kv_tokens_window"] for c in in_window), default=0)
    kv = {"pool_bytes": pool_bytes,
          "live_bytes_mean": float(np.mean(held)),
          "live_bytes_peak": float(max(held))}
    fifth = seconds / 5
    slowest = sorted(window, key=lambda k: -k["total_s"])[:6]
    say(requests_due=len(due), requests_done_in_window=done_in_window,
        completed_requests_per_s=done_in_window / seconds,
        gap_p50_ms=_ms(stats.percentile(gaps, 50)),
        gap_p95_ms=_ms(stats.percentile(gaps, 95)),
        gap_max_ms=_ms(max(gaps, default=None)), gaps=len(gaps),
        gaps_by_25ms={int(b) * 25: int(n) for b, n in zip(*np.unique(
            np.floor(np.asarray(gaps) / 0.025), return_counts=True))},
        slowest_steps_ms=[{"total": _ms(k["total_s"]), **{
            name: _ms(v) for name, v in k["s"].items() if v >= 0.001}}
            for k in slowest],
        tokens_in_window=n_tokens, ticks_in_window=len(window),
        tokens_by_fifth=[sum(stats.tokens_in(
            r["times"], w0 + i * fifth, w0 + (i + 1) * fifth) for r in reqs)
            for i in range(5)],
        tick_device_p50_ms=_ms(stats.percentile(series["tick_device_s"], 50)),
        tick_host_p50_ms=_ms(stats.percentile(series["tick_host_s"], 50)),
        tick_device_total_s=sum(series["tick_device_s"]),
        admit_total_s=sum(k["s"].get("admit", 0.0) for k in window),
        chunk_total_s=sum(k["s"].get("chunk", 0.0) for k in window),
        chunk_p50_ms=_ms(stats.percentile(
            [k["s"]["chunk"] for k in window
             if k["s"].get("chunk", 0.0) > 1e-3], 50)),
        step_total_s=sum(k["total_s"] for k in window),
        prompt_tokens_due=sum(len(r["prompt"]) for r in due),
        occupancy_mean=float(np.mean(series["occupancy"] or [0])), kv=kv,
        window_tokens_bound=window_bound, window_tokens_peak=window_peak,
        expert_load_max=max(
            (c["expert_load_max"] for c in counted), default=None))

    whole = all(len(r["times"]) == r["max_new"] for r in done)
    z = reference_slack(params, m, done, cell["check"], say)
    correct = (no_compile and whole and not lost
               and window_peak <= window_bound
               and z is not None and agrees(z))
    say(no_compile_in_window=no_compile,
        every_finished_request_whole=whole, lost=len(lost),
        **({} if z is None else {
            "token_slack_mean_std": float(z.mean()),
            "token_slack_tail_share": float((z > TAIL_AT).mean()),
            "token_slack_worst_std": float(z.max())}),
        token_slack_limits=[MEAN_SLACK, TAIL_SHARE, WORST_SLACK])

    return {
        "correct": bool(correct), "attempted": len(due),
        "failed": sum(r["lost"] is not None for r in due),
        "measured": measured, "peak_bytes": peak_bytes, "extra": {"kv": kv},
        "ctx": {"cell": cell, "series": series, "requests": reqs,
                "trace": data["trace"], "counted": counted,
                # what a traced decode tick had to read, on average
                "tick_bytes": float(np.mean([costs_afmoe.tick_bytes(
                    m, {"full": c["kv_tokens"],
                        "window": c["kv_tokens_window"]},
                    c["experts_touched"]) for c in traced]))
                if traced else None},
    }


def checked_requests(done: list[dict], check: dict) -> list[dict]:
    """The `shortest` finished requests, and the shortest finished one
    whose context (prompt and served tokens) passed `long_over`."""
    by_len = sorted(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    picked = by_len[:check["shortest"]]
    long = next((r for r in by_len[check["shortest"]:]
                 if len(r["prompt"]) + len(r["tokens"]) > check["long_over"]),
                None)
    return picked + ([long] if long is not None else [])


def reference_slack(params, m, done, check, say):
    """How far below its row's best reference logit each served token of
    the checked requests lies, in standard deviations of the checked
    rows: each request teacher-forced alone through the plain reference
    after the window (padded to a multiple of `pad_to`: a later position
    changes nothing before it). None where no long request finished: the
    check has to include one."""
    import jax.numpy as jnp

    from benchmarks.reference import afmoe as reference

    picked = checked_requests(done, check)
    if len(picked) <= check["shortest"]:
        return None
    kw = reference.settings(m)
    t = CLOCK()
    rows, toks = [], []
    for r in picked:
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
        ids = np.zeros((1, -(-len(seq) // check["pad_to"]) * check["pad_to"]),
                       np.int32)
        ids[0, :len(seq)] = seq
        p, g = len(r["prompt"]), len(r["tokens"])
        ref = reference.logits(params, jnp.asarray(ids), **kw)
        # row p-1+i predicts generated token i
        rows.append(np.asarray(ref[0, p - 1: p - 1 + g]))
        toks.append(seq[p: p + g])
    rows, toks = np.concatenate(rows), np.concatenate(toks)
    z = (rows.max(-1) - rows[np.arange(len(toks)), toks]) / rows.std()
    ends = np.cumsum([len(r["tokens"]) for r in picked])
    say(reference_s=CLOCK() - t, reference_requests=len(picked),
        reference_contexts=[len(r["prompt"]) + len(r["tokens"])
                            for r in picked],
        reference_tokens=len(z), p90=float(np.percentile(z, 90)),
        p99=float(np.percentile(z, 99)),
        not_reference_best=int((z > 0).sum()),
        worst_by_request=[float(b.max()) for b in np.split(z, ends[:-1])])
    return z
