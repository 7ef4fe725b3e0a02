"""Drives `hyperion_tpu.serve.engine.Engine` over
`models/solar_open2.py` (the `solar-open2-250b-l4-ep8` configuration:
three Kimi Delta Attention layers whose state is a row a slot, one gated
NoPE GQA layer in the paged pool, 40 of 320 experts in every layer) as
`adapters/serve_smallthinker.py` drives it over its model: the same loop
(`_drive`, imported), the same end-to-end arithmetic (`stats`), the same
trace reduction, the same series names and tick counters for the
readers, the window opened by `serve_ouro.AfterRamp` (after the ramp,
where a round of the generator's lengths begins). No serving adapter
that exists may be edited and none takes a model, a cost function or a
reference from outside, so this file brings:

  * `model_config` (the configuration file's HF keys to
    `SolarOpen2Config`) and `solar_weights`;
  * `run`, `serve_smallthinker.py`'s with this model, one pool kind,
    `costs_solar_open2` (the tick's least bytes count the state of the
    rows it advanced: the tick record's `recurrent_rows`), the state's
    share of those bytes for `state_bytes_pct`, and the check below;
  * `correct`: cell 3's rule (nothing compiled in the window, nothing
    lost, every finished request whole, and the served tokens of the
    `shortest` finished requests and of the shortest finished one whose
    context passed `long_over`, each teacher-forced alone through
    `reference/solar_open2.py`, the recurrence token by token), with
    limits of this cell's own (`agrees`).

The new model is imported at the top of `run`: on a checkout without
it the cell fails at once."""

from __future__ import annotations

import gc
import zlib

import numpy as np

from benchmarks import costs_solar_open2, spec, stats
from benchmarks.adapters.serve import CLOCK, _drive, _ms, _peak_bytes
from benchmarks.adapters.serve_afmoe import checked_requests
from benchmarks.adapters.serve_ouro import AfterRamp

# How far below its row's best reference logit a served token lies, in
# standard deviations of the checked rows (`z`), in cells 3-5's three
# terms, each limit between the system's largest reading on the chip
# (bf16 served against the float32 reference, 26 seeds on two traffic
# mixes, measured on one v5e chip) and the smallest reading
# there of what it is held against: the system served in the nearest
# precision below bf16 (every matrix rounded to fp8 e4m3, the reference
# given the bf16 weights), a bucket's pad positions entering the state,
# and the reference with one of the layer's equations wrong
# (`tests/bench_harness/solar_open2_faults.py`; PERF.md section 6
# has every reading):
#   the mean over the NEAR-TIE rows (the reference's two best logits
#     within `NEAR` std; never fewer than the cell's `check.near_rows`,
#     the rows of the smallest margin making up the number): the system
#     0.015-0.033 over 43-749 rows; pads in the state 0.370, beta
#     without its factor 2 0.410, fp8 served 0.435, no decay 2.35, the
#     conv dropped 2.69, rotary positions on the GQA layer 3.44. Limit
#     0.1, near the geometric middle of 0.033 and 0.370 (0.110);
#   the mean over all rows (131-1,500 checked tokens a run): the system
#     0.005-0.018; pads 0.399, beta 0.457, fp8 served 0.466, the others
#     2.5-3.6. Limit 0.08 (middle 0.085);
#   the worst token: the system 0.11-0.64; fp8 served 1.90, beta 2.37,
#     pads 3.31, the others 7.0-7.8. Limit 1.5: over twice the system's,
#     where a single wrong token lies several std below its row's best.
# A tick advancing a lane that is still prefilling in chunks is not
# seen at the cell's size (0.018 / 0.013 / 0.21 on the chip, inside the
# system's own readings); tests/test_solar_open2.py holds such a lane's
# state bit for bit.
NEAR, NEAR_MEAN_SLACK = 0.1, 0.1
MEAN_SLACK = 0.08
WORST_SLACK = 1.5


def near_ties(margin, at_least: int):
    """Which rows are near-ties: those whose two best reference logits
    lie within `NEAR`, or the `at_least` rows of the smallest `margin`
    where those are more. None where there are not that many rows."""
    if len(margin) < at_least:
        return None
    return margin <= max(NEAR, np.partition(margin, at_least - 1)[
        at_least - 1])


def agrees(z, margin, near_rows: int) -> bool:
    """Whether served tokens `z` below their rows' best reference
    logits, on rows whose two best reference logits lie `margin` apart,
    are the reference's own, to the three limits above."""
    near = near_ties(margin, near_rows)
    return bool(near is not None and z[near].mean() <= NEAR_MEAN_SLACK
                and z.mean() <= MEAN_SLACK and z.max() <= WORST_SLACK)


def slack_readings(z, margin, near_rows: int) -> dict:
    """What `agrees` compares with its limits, by name."""
    near = near_ties(margin, near_rows)
    return {**({} if near is None else {
                "token_slack_near_mean_std": float(z[near].mean()),
                "near_tie_rows": int(near.sum())}),
            "token_slack_mean_std": float(z.mean()),
            "token_slack_worst_std": float(z.max())}


def served(picked: list[dict]) -> list[np.ndarray]:
    """The tokens each checked request was served: what the check
    scores against the reference's rows (a fault replaces this)."""
    return [np.asarray(r["tokens"], np.int32) for r in picked]


def model_config(m: dict):
    from hyperion_tpu.models.solar_open2 import SolarOpen2Config

    la = m["linear_attn_config"]
    # what models/solar_open2.py computes, and nothing else
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0)):
        if m[key] != want:
            raise ValueError(f"models/solar_open2.py runs {key}={want}, "
                             f"the configuration has {m[key]!r}")
    if la["num_kv_heads"] is not None:
        raise ValueError("models/solar_open2.py runs KDA without grouping")
    return SolarOpen2Config(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_size=la["short_conv_kernel_size"], gate_rank=la["head_dim"],
        moe_ff_dim=m["moe_intermediate_size"],
        layer_types=tuple("full" if i in m["gqa_layers"] else "recurrent"
                          for i in m["layers_kept"]),
        n_experts=m["router_experts"], experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        route_norm=m["norm_topk_prob"],
        route_scale=float(m["routed_scaling_factor"]),
        max_len=m["max_position_embeddings"], norm_eps=m["rms_norm_eps"],
        dtype=m["torch_dtype"])


def solar_weights(model, seed: int):
    """`weights.decoder_weights` (normal(0.02) matrices in the served
    type, norm scales 1, from the seed on the device), then what the
    published layer initialises otherwise: `expert_bias` 0, `A_log` =
    log U(1, 16) and `dt_bias` = softplus^-1(dt) with dt log-uniform in
    [0.001, 0.1] (float32), the conv taps U(-0.5, 0.5)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.weights import decoder_weights

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)

    @jax.jit
    def redraw(params):
        def one(path, w):
            name = path[-1].key
            k = jax.random.fold_in(key, zlib.crc32(
                jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
            if name == "expert_bias":
                return jnp.zeros(w.shape, jnp.float32)
            if name == "A_log":
                return jnp.log(jax.random.uniform(
                    k, w.shape, jnp.float32, 1.0, 16.0))
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, w.shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))
            if name == "conv":
                return jax.random.uniform(
                    k, w.shape, jnp.float32, -0.5, 0.5).astype(w.dtype)
            return w

        return jax.tree_util.tree_map_with_path(one, params)

    return redraw(decoder_weights(model, seed))


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.models.solar_open2 import SolarOpen2
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    m = cell["model"]
    model = SolarOpen2(model_config(m))
    t = CLOCK()
    params = jax.block_until_ready(solar_weights(model, seed))
    say(weights_s=CLOCK() - t,
        parameters=sum(x.size for x in jax.tree.leaves(params)),
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(params)))

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
        kv_pool_bytes=memory["kv_pool_bytes"], state=memory["state"],
        compile_s=engine.ledger.warmup["compile_s"])

    slots = engine.cfg.slots
    sizes = costs_solar_open2.solar_sizes(m)
    kv_token_bytes = sizes["kv_bytes_per_token"]
    pool_bytes = memory["kv_pool_bytes"]
    source = AfterRamp(spec.plugin("traffic", cell["generator"]).Source(
        cell["traffic"], seed, m["vocab_size"]), cell["traffic"]["strata"])
    data = _drive(engine, source, seconds, cell, trace, trace_dir,
                  token_times, lost)
    w0, w1 = data["window"]
    no_compile = engine.compile_stats() == compiled
    peak_bytes = _peak_bytes()
    del engine          # pools and state go; the reference needs the room
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

    def least_bytes(c: dict) -> float:
        return costs_solar_open2.tick_bytes(
            m, c["kv_tokens"], c["experts_touched"], c["recurrent_rows"])

    # the engine's own tick records: the window's, then the traced ones;
    # each decode tick with the constants of the configuration and what
    # it had to move, of which the state
    in_win = [k for k in window if "device" in k["s"]]
    counted = [{**k["c"], "expert_layers": sizes["expert_layers"],
                "experts_held_all_layers":
                    sizes["expert_layers"] * sizes["experts_held"],
                **({"state_bytes": costs_solar_open2.state_bytes(
                    m, k["c"]["recurrent_rows"]),
                    "least_bytes": least_bytes(k["c"])}
                   if "recurrent_rows" in k["c"] else {})}
               for k in in_win]
    traced = [k["c"] for k in data["traced_ticks"]
              if "device" in k["s"] and "recurrent_rows" in k["c"]]
    series = {
        "gap_s": gaps,
        # of the requests whose first token fell in the window (a request
        # sent in the window may wait past its end for a slot)
        "prefill_s": [r["prefill_s"] for r in reqs
                      if r["first_token"] is not None
                      and w0 <= r["first_token"] <= w1],
        "tick_device_s": [k["s"]["device"] for k in in_win],
        "tick_host_s": [k["total_s"] - k["s"]["device"]
                        - k["s"].get("admit", 0.0) - k["s"].get("chunk", 0.0)
                        for k in in_win],
        "occupancy": [k["active"] / slots for k in window],
        # bytes the running requests hold in the GQA layers' pool over
        # the pool's bytes
        "kv_fill": [k["c"]["kv_tokens"] * kv_token_bytes / pool_bytes
                    for k in window],
    }
    held = [k["c"]["kv_tokens"] * kv_token_bytes for k in window] or [0.0]
    kv = {"pool_bytes": pool_bytes,
          "live_bytes_mean": float(np.mean(held)),
          "live_bytes_peak": float(max(held)),
          "state_bytes": memory["state"]["pool_bytes"]}
    fifth = seconds / 5
    slowest = sorted(window, key=lambda k: -k["total_s"])[:6]
    say(requests_due=len(due), requests_done_in_window=done_in_window,
        requests_done_before_window=sum(r["times"][-1] < w0 for r in done),
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
        chunk_steps_in_window=sum(
            k["s"].get("chunk", 0.0) > 1e-3 for k in window),
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
        expert_load_max=max(
            (c["expert_load_max"] for c in counted), default=None),
        traced_ticks=len(traced), traced_mean={
            k: float(np.mean([c[k] for c in traced]))
            for k in ("kv_tokens", "kv_blocks_walked", "recurrent_rows",
                      "expert_picks_held", "experts_touched")
            if traced and k in traced[0]})

    whole = all(len(r["times"]) == r["max_new"] for r in done)
    checked = reference_slack(params, m, done, cell["check"], say)
    correct = (no_compile and whole and not lost and checked is not None
               and agrees(*checked, cell["check"]["near_rows"]))
    say(no_compile_in_window=no_compile,
        every_finished_request_whole=whole, lost=len(lost),
        **({} if checked is None else slack_readings(
            *checked, cell["check"]["near_rows"])),
        token_slack_limits=[NEAR_MEAN_SLACK, MEAN_SLACK, WORST_SLACK])

    return {
        "correct": bool(correct), "attempted": len(due),
        "failed": sum(r["lost"] is not None for r in due),
        "measured": measured, "peak_bytes": peak_bytes, "extra": {"kv": kv},
        "ctx": {"cell": cell, "series": series, "requests": reqs,
                "trace": data["trace"], "counted": counted,
                # what a traced decode tick had to move, on average
                "tick_bytes": float(np.mean([least_bytes(c)
                                             for c in traced]))
                if traced else None},
    }


def reference_slack(params, m, done, check, say):
    """`(z, margin)` over the served tokens of the checked requests: how
    far below its row's best reference logit each lies, and how far
    apart that row's two best reference logits are, both in standard
    deviations of the checked rows. Each request is teacher-forced alone
    through the plain reference after the window (padded to a multiple
    of `pad_to`: a later position changes nothing before it). None where
    no long request finished: the check has to include one."""
    import jax.numpy as jnp

    from benchmarks.reference import solar_open2 as reference

    picked = checked_requests(done, check)
    if len(picked) <= check["shortest"]:
        return None
    kw = reference.settings(m)
    t = CLOCK()
    rows = []
    for r in picked:
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
        ids = np.zeros((1, -(-len(seq) // check["pad_to"]) * check["pad_to"]),
                       np.int32)
        ids[0, :len(seq)] = seq
        p, g = len(r["prompt"]), len(r["tokens"])
        # row p-1+i predicts generated token i
        ref = reference.logits(params, jnp.asarray(ids), **kw)
        rows.append(np.asarray(ref[0, p - 1:p - 1 + g]))
    rows, toks = np.concatenate(rows), np.concatenate(served(picked))
    best2 = np.partition(rows, -2, axis=-1)[:, -2:]
    z = (best2[:, 1] - rows[np.arange(len(toks)), toks]) / rows.std()
    margin = (best2[:, 1] - best2[:, 0]) / rows.std()
    ends = np.cumsum([len(r["tokens"]) for r in picked])
    say(reference_s=CLOCK() - t, reference_requests=len(picked),
        reference_contexts=[len(r["prompt"]) + len(r["tokens"])
                            for r in picked],
        reference_tokens=len(z), p90=float(np.percentile(z, 90)),
        p99=float(np.percentile(z, 99)),
        not_reference_best=int((z > 0).sum()),
        worst_by_request=[float(b.max()) for b in np.split(z, ends[:-1])])
    return z, margin
