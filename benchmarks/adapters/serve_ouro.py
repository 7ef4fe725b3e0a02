"""Drives `hyperion_tpu.serve.engine.Engine` over `models/ouro.py` (the
`ouro-2.6b` configuration: 48 layers applied four times a token over
shared weights, a cache of 4 x 48 layers behind one block table) as
`adapters/serve_smallthinker.py` drives it over its model: the same
loop (`_drive`), the same end-to-end arithmetic (`stats`), the same
trace reduction, the same series names for the readers. None of the
serving adapters that exist may be edited and none takes a model, a
cost function or a reference from outside, so this file brings:

  * `model_config` (the configuration file's HF keys to `OuroConfig`);
    the weights are `weights.decoder_weights` as they stand (the exit
    gate's kernel and bias are drawn like every matrix);
  * `run`, `serve_smallthinker.py`'s with this model, `costs_ouro`,
    one pool kind, the series `tick_device_per_layer_pass_s` (the tick
    record's `device` segment over its own `layer_passes` counter) and
    the check below;
  * `AfterRamp`: when the window may open. The generator's rule (every
    client has sent, every slot is filled) opens it while the ramp's
    eight simultaneous starts still march in step, and wherever the
    generator's rounds happen to stand. A 30 s window admits 25-29
    requests, under one round of `strata` 32 (each round one prompt
    and one output from every band of the grid): a window that
    straddles two rounds takes a random 13 of one and 14 of the next
    (10-16 prompts of the 512 bucket, by the builder's first twelve
    runs: `gap_p99_ms` 1.07 / 1.69 % in two sets of six, half its
    bound is 1.5), one that starts WITH a round takes 27 of its 32.
    So the window opens once as many requests have finished as there
    are slots (the ramp is over) AND the next request to be admitted
    begins a round. (`traffic/closed_loop.py` is not edited: the rule
    wraps its `Source`.);
  * `correct`: cell 4's form (nothing compiled in the window, nothing
    lost, every finished request whole, and the served tokens of the
    `shortest` finished requests and of the shortest finished one whose
    context passed `long_over`, each teacher-forced alone through
    `reference/ouro.py`), with limits of this cell's own (`agrees`).

The new model is imported at the top of `run`: on a checkout without
it the cell fails at once."""

from __future__ import annotations

import gc

import numpy as np

from benchmarks import costs_ouro, spec, stats
from benchmarks.adapters.serve import CLOCK, _drive, _ms, _peak_bytes
from benchmarks.adapters.serve_afmoe import _Counting, checked_requests

# How far below its row's best reference logit a served token lies, in
# standard deviations of the checked rows (`z`): cell 4's quantity and
# cell 4's three terms, with this cell's own limits. They are wide
# where cell 4's are narrow, because of what bf16 is at this depth: 192
# applications of a layer, each adding a normed (unit) update computed
# from bf16 products, leave the model's own bf16 full forward (no
# cache, no kernel) 25-26 % RMS from the float32 reference's logits
# (Mistral's 16 layers: 5 %), so the served token is the reference's
# best in about half of the rows (206 of 441) and lies 0.04-0.20 std
# under it on average, while it IS the bf16 full forward's best in
# 88-93 % of the rows and lies 0.0015-0.004 std under that (my chip
# runs, PR 34: PERF.md section 6 has every reading). Each limit is the
# geometric middle between the system's largest reading over the
# builder's 31 runs on the chip and the smallest reading, on the chip
# (five seeds), of the fault it is held against
# (`tests/bench_harness/ouro_faults.py`):
#   the mean over the NEAR-TIE rows (the reference's two best logits
#     within `NEAR` std; never fewer than the cell's `check.near_rows`,
#     the rows of the smallest margin making up the number): the system
#     0.044-0.326 (the next largest 0.286, 0.263); a cache shared
#     between the steps 0.93-1.31, every matrix rounded to fp8
#     1.72-2.47;
#   the mean over all rows: the system 0.029-0.246; a cache shared
#     between the steps 0.95-1.26 (the smallest of the faults: it
#     leaves the last step's own keys and values right), fp8 weights
#     1.67-2.76, three steps of four 2.00-3.18, the post-norms dropped
#     2.70-3.73, no norm between the steps 3.88-4.47;
#   the worst token: the system 0.44-1.38 (the next largest 1.14,
#     1.09); ONE served token that is another slot's moves neither mean
#     past its limit and lies 2.72-6.32 std below its row's best.
NEAR, NEAR_MEAN_SLACK = 0.1, 0.55
MEAN_SLACK = 0.48
WORST_SLACK = 1.9


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


def served(picked: list[dict]) -> list[np.ndarray]:
    """The tokens each checked request was served: what the check
    scores against the reference's rows (a fault replaces this)."""
    return [np.asarray(r["tokens"], np.int32) for r in picked]


def model_config(m: dict):
    from hyperion_tpu.models.ouro import OuroConfig

    if set(m["layer_types"]) != {"full_attention"} \
            or len(m["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError("models/ouro.py runs full-attention layers only, "
                         "one layer_types entry a layer")
    return OuroConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ff_dim=m["intermediate_size"], total_ut_steps=m["total_ut_steps"],
        early_exit_threshold=m["early_exit_threshold"],
        max_len=m["max_position_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], pool_layers=m["pool_layers"],
        dtype=m["torch_dtype"])


class AfterRamp:
    """The generator's `Source`, with a window that opens only once the
    ramp is over and a round of the generator's lengths begins: the
    generator's own rule, as many requests finished as there are
    slots, and the next request to be admitted (number `finished +
    slots`: the slots are full) the first, or one of the first
    `slack`, of a round of `strata`. `slack`: two requests that finish
    in one step pass the round's first by one."""

    def __init__(self, source, strata: int, slack: int = 2):
        self._source = source
        self.strata, self.slack = strata, slack
        self.finished = 0

    def __getattr__(self, name):
        return getattr(self._source, name)

    def done(self, t: float) -> None:
        self.finished += 1
        self._source.done(t)

    def window_may_open(self, t: float, active: int, slots: int) -> bool:
        return (self.finished >= slots
                and (self.finished + slots) % self.strata <= self.slack
                and self._source.window_may_open(t, active, slots))


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.models.ouro import Ouro
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    from benchmarks.weights import decoder_weights

    m = cell["model"]
    model = Ouro(model_config(m))
    t = CLOCK()
    params = jax.block_until_ready(decoder_weights(model, seed))
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
        kv_pool_bytes=memory["kv_pool_bytes"],
        kv_bytes_per_token=memory["kv_bytes_per_token"],
        compile_s=engine.ledger.warmup["compile_s"])

    slots = engine.cfg.slots
    sizes = costs_ouro.ouro_sizes(m)
    kv_token_bytes = sizes["kv_bytes_per_token"]
    pool_bytes = memory["kv_pool_bytes"]
    counting = _Counting(engine)
    source = AfterRamp(spec.plugin("traffic", cell["generator"]).Source(
        cell["traffic"], seed, m["vocab_size"]), cell["traffic"]["strata"])
    data = _drive(counting, source, seconds, cell, trace, trace_dir,
                  token_times, lost)
    w0, w1 = data["window"]
    no_compile = engine.compile_stats() == compiled
    bucket = {r["id"]: engine.bucket(len(r["prompt"]))
              for r in data["requests"]}
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

    # the engine's own tick records: the window's, then the traced ones
    records = [r for t, r in counting.records if w0 <= t <= w1]
    ticked = [r for r in records if "device" in r["s"]]
    traced = [r["c"] for t, r in counting.records
              if t > w1 and "device" in r["s"]]
    in_win = [k for k in window if "device" in k["s"]]
    series = {
        "gap_s": gaps,
        # of the requests whose first token fell in the window
        "prefill_s": [r["prefill_s"] for r in reqs
                      if r["first_token"] is not None
                      and w0 <= r["first_token"] <= w1],
        "tick_device_s": [k["s"]["device"] for k in in_win],
        "tick_host_s": [k["total_s"] - k["s"]["device"]
                        - k["s"].get("admit", 0.0) - k["s"].get("chunk", 0.0)
                        for k in in_win],
        "occupancy": [k["active"] / slots for k in window],
        # bytes the running requests hold over the bytes of the pool
        "kv_fill": [r["c"]["kv_tokens"] * kv_token_bytes / pool_bytes
                    for r in records],
        # a tick's device segment over the cache layers it wrote and
        # read (the record's own counter: loop steps x layers); empty
        # where the program counts none
        "tick_device_per_layer_pass_s": [
            r["s"]["device"] / r["c"]["layer_passes"] for r in ticked
            if r["c"].get("layer_passes")],
    }
    held = [r["c"]["kv_tokens"] * kv_token_bytes for r in records] or [0.0]
    kv = {"pool_bytes": pool_bytes,
          "live_bytes_mean": float(np.mean(held)),
          "live_bytes_peak": float(max(held))}
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
        tokens_by_fifth=[sum(stats.tokens_in(
            r["times"], w0 + i * fifth, w0 + (i + 1) * fifth) for r in reqs)
            for i in range(5)],
        tick_device_p50_ms=_ms(stats.percentile(series["tick_device_s"], 50)),
        tick_host_p50_ms=_ms(stats.percentile(series["tick_host_s"], 50)),
        tick_device_total_s=sum(series["tick_device_s"]),
        admit_total_s=sum(k["s"].get("admit", 0.0) for k in window),
        step_total_s=sum(k["total_s"] for k in window),
        prefill_buckets={int(b): int(n) for b, n in zip(*np.unique(
            [bucket[r["id"]] for r in reqs
             if r["first_token"] is not None
             and w0 <= r["first_token"] <= w1], return_counts=True))},
        prompt_tokens_due=sum(len(r["prompt"]) for r in due),
        occupancy_mean=float(np.mean(series["occupancy"] or [0])), kv=kv,
        loop_steps=ticked[-1]["c"].get("loop_steps") if ticked else None,
        layer_passes=ticked[-1]["c"].get("layer_passes") if ticked else None,
        traced_ticks=len(traced), traced_mean={
            k: float(np.mean([c[k] for c in traced]))
            for k in ("kv_tokens", "kv_blocks_walked") if traced})

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
                "trace": data["trace"],
                "counted": [r["c"] for r in ticked],
                # what a traced decode tick had to read, on average
                "tick_bytes": float(np.mean([costs_ouro.tick_bytes(
                    m, c["kv_tokens"], slots) for c in traced]))
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
    applied to the rows that predicted a served token only. None where
    no long request finished: the check has to include one."""
    import jax.numpy as jnp

    from benchmarks.reference import ouro as reference

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
