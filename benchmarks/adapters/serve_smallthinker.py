"""Drives `hyperion_tpu.serve.engine.Engine` over
`models/smallthinker.py` (the `smallthinker-21b-l8` configuration) as
`adapters/serve_afmoe.py` drives it over `models/afmoe.py`: the same
loop (`_drive`), the same end-to-end arithmetic (`stats`), the same
trace reduction, the same series names and tick counters for the
readers. Neither of the two serving adapters that exist may be edited
and neither takes a model, a cost function or a reference from
outside, so this file brings:

  * `model_config` (the configuration file's HF keys to
    `SmallthinkerConfig`); the weights are `weights.decoder_weights`
    as they stand (the model has no leaf that is neither a matrix nor a
    norm scale);
  * `run`, `serve_afmoe.py`'s with this model, `costs_smallthinker`
    and the check below (`_Counting` and `checked_requests` are
    imported from it);
  * `correct`: cell 3's rule (nothing compiled in the window, nothing
    lost, every finished request whole, the windowed pool under its
    bound, and the served tokens of the `shortest` finished requests
    and of the shortest finished one whose context passed `long_over`,
    each teacher-forced alone through `reference/smallthinker.py`), with
    limits of this cell's own (`agrees`: the mean shortfall over the
    rows where the reference's two best logits nearly tie, the mean
    over all rows, the worst token), each set from this cell's own
    readings on the chip.

The new model is imported at the top of `run`: on a checkout without
it the cell fails at once."""

from __future__ import annotations

import gc

import numpy as np

from benchmarks import costs_smallthinker, spec, stats
from benchmarks.adapters.serve import CLOCK, _drive, _ms, _peak_bytes
from benchmarks.adapters.serve_afmoe import _Counting, checked_requests

# How far below its row's best reference logit a served token lies, in
# standard deviations of the checked rows (`z`): cell 3's quantity, and
# none of cell 3's limits. Its plain mean cannot decide here. Greedy
# decoding on random weights locks into repetitions whose rows have one
# logit far above the rest: such rows flip under no rounding, and how
# many of a run's 489-1541 checked rows they are differs by run, so the
# mean over all rows reads 0-0.0023 for the system and 0.0022-0.075 for
# weights rounded to fp8: the two meet (PERF.md section 6, PR 31). The
# rows that can show a small error are the NEAR-TIES, where the
# reference's own two best logits lie within `NEAR` std (78-781 rows a
# run). Three terms, each set between the system's largest reading over
# the builder's 41 runs and the smallest reading, on the chip, of the
# fault it is held against (PERF.md section 6 has every reading):
#   the mean over the near-tie rows: what moves every logit a little.
#     The system at most 0.0040 (one run of 41; the next 0.0025); fp8
#     weights 0.0125 at the least over ten seeds; of the equations the
#     least seen is sigmoid weights at 0.0094 (one seed of ten, the next
#     0.0246), then the router fed the normed input 0.0127, rotary
#     positions left off the sliding layers 0.0144, SiLU 0.021. The
#     limit is their geometric middle. Never over fewer rows than the
#     cell's `check.near_rows` (100): where fewer lie within `NEAR`,
#     that many of the smallest margin are taken, so the term is never
#     empty and one flipped row moves it by a hundredth of its own `z`
#     at most;
#   the mean over all rows: a fault that moves logits by more than a
#     near-tie's width shows on rows of any margin: rotary positions on
#     the full layers 0.10 at the least (what cell 3's check cannot
#     see), a router fed the post-attention state 0.21; the system at
#     most 0.0023: the geometric middle again;
#   the worst token: ONE served token that is not the model's (another
#     slot's: `tests/bench_harness/smallthinker_faults.py`
#     `another_slots_token`) moves neither mean past its limit and lies
#     3.58-5.77 std below its row's best over six seeds; of the 5,754
#     such swaps there could be among those runs' checked requests the
#     lowest reads 0.94. The system's worst token reads 0.22 at most.
NEAR, NEAR_MEAN_SLACK = 0.1, 0.006
MEAN_SLACK = 0.015
WORST_SLACK = 0.7


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
    are the reference's own, to the three limits above (the near-tie
    term over `near_rows` rows or more)."""
    near = near_ties(margin, near_rows)
    return bool(near is not None and z[near].mean() <= NEAR_MEAN_SLACK
                and z.mean() <= MEAN_SLACK and z.max() <= WORST_SLACK)


def served(picked: list[dict]) -> list[np.ndarray]:
    """The tokens each checked request was served: what the check
    scores against the reference's rows (a fault replaces this)."""
    return [np.asarray(r["tokens"], np.int32) for r in picked]


def model_config(m: dict):
    from hyperion_tpu.models.smallthinker import SmallthinkerConfig

    kept = m["layers_kept"]
    return SmallthinkerConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        moe_ff_dim=m["moe_ffn_hidden_size"],
        n_experts=m["moe_num_primary_experts"],
        top_k=m["moe_num_active_primary_experts"],
        sliding_window_layout=tuple(
            m["sliding_window_layout"][i] for i in kept),
        rope_layout=tuple(m["rope_layout"][i] for i in kept),
        sliding_window=m["sliding_window_size"],
        max_len=m["max_position_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], dtype=m["torch_dtype"])


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.models.smallthinker import Smallthinker
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    from benchmarks.weights import decoder_weights

    m = cell["model"]
    model = Smallthinker(model_config(m))
    t = CLOCK()
    params = jax.block_until_ready(decoder_weights(model, seed))
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
    sizes = costs_smallthinker.smallthinker_sizes(m)
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
                    sizes["expert_layers"] * sizes["experts"]}
               for t, r in counting.records
               if w0 <= t <= w1 and "device" in r["s"]]
    traced = [r["c"] for t, r in counting.records
              if t > w1 and "device" in r["s"]
              and "experts_touched" in r["c"]]
    in_win = [k for k in window if "device" in k["s"]]
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
        window_tokens_bound=window_bound, window_tokens_peak=window_peak,
        expert_load_max=max(
            (c["expert_load_max"] for c in counted), default=None),
        # what the traced ticks read, on average: beside the trace's
        # seconds by scope these give the kernel's share of HBM on the
        # blocks it walked
        traced_ticks=len(traced), traced_mean={
            k: float(np.mean([c[k] for c in traced]))
            for k in ("kv_tokens", "kv_tokens_window", "kv_blocks_walked",
                      "kv_blocks_walked_window", "expert_picks_held",
                      "experts_touched") if traced and k in traced[0]})

    whole = all(len(r["times"]) == r["max_new"] for r in done)
    checked = reference_slack(params, m, done, cell["check"], say)
    correct = (no_compile and whole and not lost
               and window_peak <= window_bound
               and checked is not None
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
                # what a traced decode tick had to read, on average
                "tick_bytes": float(np.mean([costs_smallthinker.tick_bytes(
                    m, {"full": c["kv_tokens"],
                        "window": c["kv_tokens_window"]},
                    c["experts_touched"]) for c in traced]))
                if traced else None},
    }


def slack_readings(z, margin, near_rows: int) -> dict:
    """What `agrees` compares with its limits, by name."""
    near = near_ties(margin, near_rows)
    return {**({} if near is None else {
                "token_slack_near_mean_std": float(z[near].mean()),
                "near_tie_rows": int(near.sum())}),
            "token_slack_mean_std": float(z.mean()),
            "token_slack_worst_std": float(z.max())}


def reference_slack(params, m, done, check, say):
    """`(z, margin)` over the served tokens of the checked requests: how
    far below its row's best reference logit each lies, and how far
    apart that row's two best reference logits are, both in standard
    deviations of the checked rows. Each request is teacher-forced alone
    through the plain reference after the window (padded to a multiple
    of `pad_to`: a later position changes nothing before it), the head
    applied to the rows that predicted a served token only (151936
    logits a row). None where no long request finished: the check has
    to include one."""
    import jax.numpy as jnp

    from benchmarks.reference import smallthinker as reference

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
        ref = reference.logits(params, jnp.asarray(ids), **kw,
                               rows=(p - 1, g))
        rows.append(np.asarray(ref[0]))
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
