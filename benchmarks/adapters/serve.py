"""Drives `hyperion_tpu.serve.engine.Engine` in this process, as
`serve/loadgen.py` does: submit what is due, then `engine.step()`.
Differs from `run_load` in three ways: a request is timed from when it
was DUE, lengths come from a generator's data file, and the run lasts a
window of fixed length instead of draining."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import costs, spec, stats, trace_reduce

CLOCK = time.monotonic      # the engine's own clock (utils/clock.py)
# tickprof segments in the order `Engine.step` runs them
STEP_ORDER = ("queue_pop", "admit", "chunk", "draft", "bt_upload",
              "device", "accept", "slo")
# a served token's reference logit lies within this many standard
# deviations of its row's best (chip_smoke.py's TOKEN_SLACK: measured at
# most 0.096 on the chip in PR 21; a wrong token lies about 4 below)
TOKEN_SLACK = 0.6


def model_config(m: dict):
    from hyperion_tpu.models.llama import LlamaConfig

    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("head_dim x heads != hidden_size: models/llama.py "
                         "derives the head size from the two")
    return LlamaConfig(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], ff_dim=m["intermediate_size"],
        max_len=m["max_position_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], remat=False,
        dtype=m["torch_dtype"])


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str, t_start: float, say) -> dict:
    import jax

    from hyperion_tpu.models.llama import Llama
    from hyperion_tpu.serve.engine import Engine, EngineConfig

    from benchmarks.weights import decoder_weights

    m = cell["model"]
    model = Llama(model_config(m))
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
    # the prefill ladder up to the longest prompt the traffic sends
    engine.warmup([cell["traffic"]["prompt"]["max"]])
    compiled = engine.compile_stats()
    say(warmup_s=CLOCK() - t, executables=compiled)

    slots = engine.cfg.slots
    pool_tokens = engine.cfg.num_blocks * engine.cfg.block_size
    source = spec.plugin("traffic", cell["generator"]).Source(
        cell["traffic"], seed, m["vocab_size"])
    data = _drive(engine, source, seconds, cell, trace, trace_dir,
                  token_times, lost)
    w0, w1 = data["window"]
    no_compile = engine.compile_stats() == compiled
    peak_bytes = _peak_bytes()
    del engine          # the pool goes; the reference needs the room
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
    in_win = [k for k in window if "device" in k["s"]]
    series = {
        "gap_s": gaps,
        "prefill_s": [r["prefill_s"] for r in due
                      if r["first_token"] is not None],
        "tick_device_s": [k["s"]["device"] for k in in_win],
        "tick_host_s": [k["total_s"] - k["s"]["device"]
                        - k["s"].get("admit", 0.0) - k["s"].get("chunk", 0.0)
                        for k in in_win],
        "occupancy": [k["active"] / slots for k in window],
        "kv_fill": [k["kv_tokens"] / pool_tokens for k in window],
    }
    kv_token_bytes = costs.decoder_sizes(m)["kv_bytes_per_token"]
    kv = {"pool_bytes": pool_tokens * kv_token_bytes,
          "live_bytes_mean": float(np.mean(
              [k["kv_tokens"] for k in window] or [0])) * kv_token_bytes,
          "live_bytes_peak": max(
              [k["kv_tokens"] for k in window] or [0]) * kv_token_bytes}
    fifth = seconds / 5
    slowest = sorted(window, key=lambda k: -k["total_s"])[:6]
    # every run says where its window went, so that a rate that reads
    # off can be set beside its host share and its prefill share
    say(requests_due=len(due), requests_done_in_window=done_in_window,
        completed_requests_per_s=done_in_window / seconds,
        gap_p50_ms=_ms(stats.percentile(gaps, 50)),
        gap_p95_ms=_ms(stats.percentile(gaps, 95)),
        gap_max_ms=_ms(max(gaps, default=None)), gaps=len(gaps),
        # gaps by 25 ms: the modes (a tick; a tick and a prefill of each
        # bucket) and whatever lies beyond them
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
        prompt_tokens_due=sum(len(r["prompt"]) for r in due),
        occupancy_mean=float(np.mean(series["occupancy"] or [0])), kv=kv)

    whole = all(len(r["times"]) == r["max_new"] for r in done)
    slack = _reference_slack(params, m, done, cell["check"], say)
    # a closed loop below the queue's capacity: nothing may be rejected
    # or time out
    correct = (no_compile and whole and not lost
               and slack is not None and slack <= TOKEN_SLACK)
    say(no_compile_in_window=no_compile,
        every_finished_request_whole=whole, token_slack_std=slack,
        lost=len(lost))

    traced = [k for k in data["traced_ticks"] if "device" in k["s"]]
    return {
        "correct": bool(correct), "attempted": len(due),
        "failed": sum(r["lost"] is not None for r in due),
        "measured": measured, "peak_bytes": peak_bytes, "extra": {"kv": kv},
        "ctx": {"cell": cell, "series": series, "requests": reqs,
                "trace": data["trace"],
                # the engine's tick records' counters, a decode tick of
                # the window each
                "counted": [k["c"] for k in in_win],
                # what a traced decode tick had to read, on average
                "tick_bytes": float(np.mean([costs.tick_bytes(
                    m, k["kv_tokens"]) for k in traced])) if traced else None},
    }


def _ms(x):
    return None if x is None else 1e3 * x


def _peak_bytes() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def _drive(engine, source, seconds, cell, trace, trace_dir,
           token_times, lost) -> dict:
    """Set-up's ramp, the window, and in a traced run a few seconds more
    under the profiler. Starting and stopping the profiler stalls the
    loop for seconds, so the trace is taken after the window, under the
    same load: the window's host-clock series stay clean."""
    from hyperion_tpu.serve.queue import Request

    slots = engine.cfg.slots
    recs: dict[str, dict] = {}
    live: dict[str, dict] = {}
    ticks: list[dict] = []
    traced_ticks: list[dict] = []
    step_segments: dict[int, list] = {}
    steps = 0
    t0 = CLOCK()

    def turn(record: bool, tracing: bool) -> None:
        """Submit what is due, then one engine step (or a short wait)."""
        nonlocal steps
        room = engine.cfg.queue_capacity - len(engine.queue)
        for item in source.pop_due(CLOCK() - t0, room):
            rid = f"r{len(recs)}"
            req = Request(prompt_ids=item["prompt"],
                          max_new_tokens=item["max_new"], id=rid)
            token_times[rid] = []
            recs[rid] = {"req": req, "due": t0 + item["due"],
                         "max_new": item["max_new"]}
            live[rid] = recs[rid]
            engine.submit(req)
        if engine.idle:
            with trace_reduce.span(tracing, "bench.wait_for_arrival"):
                nxt = source.next_due(CLOCK() - t0)
                if nxt is not None:
                    time.sleep(min(max(nxt - (CLOCK() - t0), 0.0), 0.005))
            return
        with trace_reduce.span(tracing, f"bench.step:{steps}"):
            emitted = engine.step()
        end = CLOCK()
        for ev in emitted:
            if ev.finished:
                live.pop(ev.request.id, None)
                if ev.kind == "token":
                    source.done(end - t0)
        if record or tracing:
            prof = engine.tickprof.tail(1)[0]
            (traced_ticks if tracing else ticks).append({
                "t": end, "total_s": prof["total_s"], "s": prof["s"],
                "c": prof["c"], "active": engine.n_active,
                "kv_tokens": sum(
                    len(r["req"].prompt_ids) + len(r["req"].tokens)
                    for r in live.values() if r["req"].tokens)})
            if tracing:
                step_segments[steps] = [
                    (k, prof["s"][k]) for k in STEP_ORDER if k in prof["s"]]
        steps += 1

    while not source.window_may_open(CLOCK() - t0, engine.n_active, slots):
        turn(False, False)
    w0 = CLOCK()
    w1 = w0 + seconds
    while CLOCK() < w1:
        turn(True, False)

    reduced = None
    if trace:
        with trace_reduce.capture(trace_dir):
            until = CLOCK() + cell["trace_s"]
            while CLOCK() < until:
                turn(False, True)
        reduced = trace_reduce.reduce_dir(
            trace_dir, step_segments=step_segments)
    requests = []
    for rid, r in recs.items():
        req, times = r["req"], token_times[rid]
        requests.append({
            "id": rid, "due": r["due"],
            "max_new": r["max_new"], "prompt": req.prompt_ids,
            "tokens": list(req.tokens), "times": times,
            "first_token": times[0] if times else None,
            "finished": req.status == "done", "lost": lost.get(rid),
            "prefill_s": req.prefill_s})
    return {"window": (w0, w1), "ticks": ticks, "traced_ticks": traced_ticks,
            "requests": requests, "trace": reduced}


def _reference_slack(params, m, done, check, say) -> float | None:
    """The shortest finished requests, teacher-forced through the plain
    reference after the window; None if none is short enough."""
    import jax.numpy as jnp

    from benchmarks.reference import decoder

    L = check["pad_to"]
    short = sorted((r for r in done
                    if len(r["prompt"]) + len(r["tokens"]) <= L),
                   key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    short = short[:check["requests"]]
    if not short:
        return None
    ids = np.zeros((len(short), L), np.int32)
    rows = []
    for b, r in enumerate(short):
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
        ids[b, :len(seq)] = seq
        rows.append((len(r["prompt"]), len(r["tokens"]), seq))
    t = CLOCK()
    ref = decoder.logits(
        params, jnp.asarray(ids), n_layers=m["num_hidden_layers"],
        theta=m["rope_theta"], eps=m["rms_norm_eps"])
    slack = decoder.token_slack(np.asarray(ref), rows)
    say(reference_s=CLOCK() - t, reference_requests=len(short),
        reference_tokens=sum(g for _, g, _ in rows))
    return slack
