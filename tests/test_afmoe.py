"""`models/afmoe.py` (Arcee Trinity's block), the dropless expert layer
(`ops/moe.py` `dropless_moe`) and the serving cache by layer kind,
against the plain reference `benchmarks/reference/afmoe.py`: float32,
tiny sizes (window 8, contexts to 56, 8-16 experts)."""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_harness.afmoe_faults import EQUATIONS, fault as wrong_reference
from benchmarks.reference import afmoe as reference
from hyperion_tpu.models.afmoe import FULL, SLIDING, Afmoe, afmoe_tiny_config
from hyperion_tpu.models.llama import (
    Llama,
    init_paged_cache,
    llama_tiny_config,
    window_view_blocks,
)
from hyperion_tpu.ops.moe import dropless_moe, sigmoid_topk_route
from hyperion_tpu.serve.engine import Engine, EngineConfig
from hyperion_tpu.serve.queue import Request

TOL = 1e-4


def ref_kw(cfg, **over):
    return {**dict(
        layer_types=cfg.layer_types, window=cfg.sliding_window,
        theta=cfg.rope_theta, eps=cfg.norm_eps, held=cfg.experts_held,
        top_k=cfg.top_k, route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, mup=cfg.mup_enabled), **over}


def make(cfg, seed=1):
    """Weights with every vector (norm scales, `expert_bias`) moved off
    its initial value, so that each of them matters."""
    model = Afmoe(cfg)
    params = model.init_params(jax.random.key(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    leaves = [x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
              if x.ndim == 1 else 20 * x for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


@pytest.fixture(scope="module")
def tiny():
    cfg = afmoe_tiny_config(experts_held=(2, 4))
    return (cfg, *make(cfg))


def ids_of(cfg, n, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, n), 1,
                              cfg.vocab_size)


# ------------------------------------------------------------- the model


@pytest.mark.parametrize("held", [(0, 8), (2, 4), (7, 1)])
def test_model_agrees_with_the_plain_reference(held):
    cfg = afmoe_tiny_config(experts_held=held)
    model, params = make(cfg)
    ids = ids_of(cfg, 40, batch=2)
    got = model.apply({"params": params}, ids)
    want = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(want.std()) > 0.5     # not a comparison of zeros


@pytest.mark.parametrize("fault", EQUATIONS)
def test_each_fault_fails_the_comparison(tiny, fault):
    """What the comparison has to see: the window mask, rotary positions
    kept off the full layers, the gate, the shared expert, `route_scale`
    and the QK-norm each move the logits far past the tolerance."""
    cfg, model, params = tiny
    ids = ids_of(cfg, 40)
    got = model.apply({"params": params}, ids)
    with wrong_reference(fault):
        wrong = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - wrong).max()) > 100 * TOL


def paged(cfg, slots, max_len, bs):
    mb = max_len // bs
    cache = init_paged_cache(
        cfg, {"full": slots * mb + 1, "window": slots * mb + 1}, bs)
    table = 1 + np.arange(slots * mb, dtype=np.int32).reshape(slots, mb)
    return cache, {"full": jnp.asarray(table), "window": jnp.asarray(table)}


@pytest.mark.parametrize("chunk", [0, 8], ids=["one-shot", "chunked"])
def test_paged_prefill_then_decode_agrees_with_the_full_forward(tiny, chunk):
    """Contexts several windows long through the by-kind paged cache:
    prefill (whole, or in chunks of 8), then token by token; every
    position's logits against the reference's one full forward. The
    windowed layers read a slice of their table (3 blocks of 16 at the
    tick), not the chain."""
    cfg, model, params = tiny
    bs, P, total = 4, 37, 56
    assert window_view_blocks(cfg.sliding_window, 1, bs) == 3
    ids = ids_of(cfg, total, seed=5)
    want = reference.logits(params, ids, **ref_kw(cfg))[0]
    cache, tables = paged(cfg, 1, cfg.max_len, bs)
    v = {"params": params}
    step = chunk or P
    got = []
    for start in range(0, P, step):
        piece = ids[:, start:min(P, start + step)]
        out, cache = model.apply(v, piece, cache=cache,
                                 cache_index=jnp.int32(start),
                                 block_tables=tables)
        got.append(out[0])
    for p in range(P, total):
        out, cache = model.apply(v, ids[:, p:p + 1], cache=cache,
                                 cache_index=jnp.asarray([p], jnp.int32),
                                 block_tables=tables)
        got.append(out[0])
    got = jnp.concatenate(got)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < TOL


def test_a_freed_block_behind_the_window_changes_nothing(tiny):
    """A windowed layer's table entries behind every query's window may
    point anywhere (the engine zeroes them when it frees the block): the
    read masks by position."""
    cfg, model, params = tiny
    bs, P = 4, 40
    ids = ids_of(cfg, P + 1, seed=6)
    cache, tables = paged(cfg, 1, cfg.max_len, bs)
    v = {"params": params}
    _, cache = model.apply(v, ids[:, :P], cache=cache,
                           cache_index=jnp.int32(0), block_tables=tables)
    kw = dict(cache=cache, cache_index=jnp.asarray([P], jnp.int32))
    whole, _ = model.apply(v, ids[:, P:], block_tables=tables, **kw)
    gone = (P - cfg.sliding_window + 1) // bs
    freed = {**tables, "window": tables["window"].at[:, :gone].set(0)}
    cut, _ = model.apply(v, ids[:, P:], block_tables=freed, **kw)
    assert float(jnp.abs(whole - cut).max()) == 0.0


# ------------------------------------------------------ the expert layer


def moe_params(key, d=16, f=8, experts=16):
    k = jax.random.split(key, 5)
    return {"router": jax.random.normal(k[0], (d, experts)),
            "expert_bias": jnp.zeros((experts,)),
            "gate": jax.random.normal(k[1], (experts, d, f)) / 4,
            "up": jax.random.normal(k[2], (experts, d, f)) / 4,
            "down": jax.random.normal(k[3], (experts, f, d)) / 4}


def share(p, first, count):
    return {**p, **{k: p[k][first:first + count]
                    for k in ("gate", "up", "down")}}


def test_the_shares_add_up_to_the_uncut_layer():
    """Every share's routed part (what each of the chips that divide a
    layer computes) adds up to what one chip holding all 16 experts
    gives; the shared expert is whole on every chip and counts once
    (`test_model_agrees...` holds it inside the model)."""
    p = moe_params(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (24, 16))
    kw = dict(top_k=4, route_norm=True, route_scale=2.448)
    whole, load = dropless_moe(x, p, held=(0, 16), **kw)
    parts = [dropless_moe(x, share(p, first, count), held=(first, count),
                          **kw) for first, count in
             ((0, 4), (4, 4), (8, 2), (10, 6))]
    assert float(jnp.abs(sum(y for y, _ in parts) - whole).max()) < 1e-5
    assert int(load.sum()) == 24 * 4
    assert sum(int(ld.sum()) for _, ld in parts) == 24 * 4
    # and against the plain form: every expert on every token, weighted
    want = reference.moe(
        x[None], {"router": p["router"], "expert_bias": p["expert_bias"],
                  "experts_gate": p["gate"], "experts_up": p["up"],
                  "experts_down": p["down"],
                  "shared": {n: {"kernel": jnp.zeros((16, 8)[::s])}
                             for n, s in (("gate_proj", 1), ("up_proj", 1),
                                          ("down_proj", -1))}},
        held=(0, 16), **kw)[0]
    assert float(jnp.abs(whole - want).max()) < 1e-5


def test_no_token_is_dropped_when_every_row_picks_the_same_expert():
    """No capacity: 40 rows that all pick expert 3 first all get its
    output (a capacity layer at factor 1.25 would serve 13 of them)."""
    p = moe_params(jax.random.key(2))
    p["expert_bias"] = p["expert_bias"].at[3].set(10.0)
    x = jax.random.normal(jax.random.key(3), (40, 16))
    y, load = dropless_moe(x, p, held=(0, 16), top_k=1, route_norm=False,
                           route_scale=1.0)
    assert int(load[:, 3].sum()) == 40 and int(load.sum()) == 40
    s = jax.nn.sigmoid(x @ p["router"])[:, 3:4]
    want = s * reference.swiglu(x, p["gate"][3], p["up"][3], p["down"][3])
    assert float(jnp.abs(y - want).max()) < 1e-5
    assert float(jnp.abs(y).min(axis=-1).max()) > 0    # every row served


@pytest.mark.parametrize("case", [
    "bias_moves_the_pick_not_the_weight", "route_norm", "route_scale",
    "float32_scores"])
def test_router(case):
    key = jax.random.key(4)
    x = jax.random.normal(key, (12, 16))
    w = jax.random.normal(jax.random.key(5), (16, 8))
    zero = jnp.zeros((8,))
    kw = dict(top_k=2, route_norm=False, route_scale=1.0)
    scores = jax.nn.sigmoid(x @ w)
    picked, wt = sigmoid_topk_route(x, w, zero, **kw)
    if case == "bias_moves_the_pick_not_the_weight":
        bias = zero.at[5].set(10.0)
        picked_b, wt_b = sigmoid_topk_route(x, w, bias, **kw)
        assert bool((picked_b[:, 0] == 5).all())
        assert not bool((picked[:, 0] == 5).all())
        # the weight is the score without the bias
        assert jnp.allclose(wt_b[:, 0], scores[:, 5], atol=1e-6)
        assert float(wt_b.max()) <= 1.0
    elif case == "route_norm":
        _, normed = sigmoid_topk_route(
            x, w, zero, top_k=2, route_norm=True, route_scale=1.0)
        assert jnp.allclose(normed.sum(-1), 1.0, atol=1e-6)
        assert jnp.allclose(normed, wt / wt.sum(-1, keepdims=True),
                            atol=1e-6)
    elif case == "route_scale":
        _, scaled = sigmoid_topk_route(
            x, w, zero, top_k=2, route_norm=True, route_scale=2.448)
        assert jnp.allclose(scaled.sum(-1), 2.448, atol=1e-5)
    else:
        # bf16 inputs, float32 scores: the weights carry more than
        # bf16's 8 bits of mantissa
        _, wt16 = sigmoid_topk_route(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), zero, **kw)
        assert wt16.dtype == jnp.float32
        assert bool((wt16 != wt16.astype(jnp.bfloat16)).any())


# ---------------------------------------------------------- the engine


def serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    bound = eng.cfg.slots * eng._hold["window"]
    peak = 0
    while not eng.idle:
        eng.step()
        win = eng._mgrs["window"]
        assert win.in_use <= bound
        # a slot's windowed chain never passes its hold
        for q in eng._allocs["window"]:
            assert q is None or len(q.blocks) <= eng._hold["window"]
        peak = max(peak, win.in_use)
    return peak


@pytest.mark.parametrize("admission", ["reserve", "optimistic"])
def test_engine_serves_short_and_long_requests_in_one_queue(tiny, admission):
    """Short and long requests through the same jits and block managers
    as Llama's: served tokens are the reference's greedy tokens, the
    windowed pool stays under its bound at every step, a long request
    holds less than its whole chain there, and both managers are empty
    and clean after the drain."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=8,
        prefix_cache=False, admission=admission))
    assert eng._hold == {"full": 16, "window": 6}
    assert {k: m.num_blocks for k, m in eng._mgrs.items()} == \
        {"full": 49, "window": 19}
    eng.warmup([8])
    compiled = eng.compile_stats()
    rng = np.random.default_rng(0)
    reqs = [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g, id=f"r{i}")
            for i, (n, g) in enumerate(
                [(5, 6), (30, 20), (44, 12), (7, 3), (21, 30), (3, 40)])]
    peak = serve(eng, reqs)
    assert 0 < peak <= 3 * 6
    assert eng.compile_stats() == compiled
    for mgr in eng._mgrs.values():
        mgr.check()
        assert mgr.in_use == 0 and mgr.reserved == 0
    for r in reqs:
        assert r.status == "done" and len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt_ids, np.asarray(r.tokens, np.int32)])
        want = reference.logits(params, jnp.asarray(seq[None]),
                                **ref_kw(cfg))[0]
        P = len(r.prompt_ids)
        assert (np.asarray(want[P - 1:P - 1 + len(r.tokens)].argmax(-1))
                == np.asarray(r.tokens)).all(), r.id
    # the tick record counted by kind and by expert
    c = [r["c"] for r in eng.tickprof.tail(256) if "device" in r["s"]]
    assert all({"kv_tokens", "kv_tokens_window", "expert_picks_held",
                "experts_touched", "expert_load_max"} <= set(x) for x in c)
    assert any(x["kv_tokens_window"] < x["kv_tokens"] for x in c)
    assert all(x["experts_touched"] <= x["expert_picks_held"]
               <= 3 * cfg.top_k * 4 for x in c)
    snap = eng.tickprof.snapshot(window_s=3600)
    assert snap["experts"]["ticks"] == len(c)
    assert "kv_tokens_window" in snap["counters"]
    ledger = eng.memory_ledger()
    assert set(ledger["kv_by_kind"]) == {"full", "window"}
    assert ledger["kv_pool_bytes"] == sum(
        v["pool_bytes"] for v in ledger["kv_by_kind"].values())


def test_a_windowed_chain_lets_blocks_go_within_the_step(tiny):
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=1, max_len=64, block_size=4, prefill_chunk=8,
        prefix_cache=False))
    eng.submit(Request(prompt_ids=np.arange(1, 41, dtype=np.int32),
                       max_new_tokens=20, id="long"))
    while not eng.idle:
        eng.step()
        full, win = eng._seqs[0], eng._allocs["window"][0]
        if win is None:
            continue
        # every block wholly behind the next query's window is gone, in
        # the table too
        gone = max(0, full.n_filled - cfg.sliding_window + 1) // 4
        assert win.first == gone
        row = eng._chunking[0]["rows"]["window"] if 0 in eng._chunking \
            else eng._bts["window"][0]
        assert not row[:gone].any()
        assert eng._mgrs["window"].in_use == len(win.blocks)
        assert len(full.blocks) >= -(-full.n_filled // 4)   # full keeps all


def test_a_held_shares_steps_count_every_pick_row_by_form(tiny):
    """`expert_rows_kernel` / `expert_rows_ragged` of `afmoe`: the rows
    of the grouped products are every (token, pick) pair, held here or
    not (rows for absent experts sort last and are never multiplied),
    over the EXPERT layers only (the leading dense layer has none).
    Off a TPU all of them go through `ragged_dot`."""
    cfg, model, params = tiny
    assert cfg.expert_step == {
        "layers": cfg.n_layers - cfg.n_dense_layers,
        "groups": cfg.experts_held[1], "top_k": cfg.top_k,
        "k": cfg.d_model, "n": cfg.moe_ff_dim, "itemsize": 4}
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=8,
        prefix_cache=False))
    rng = np.random.default_rng(1)
    serve(eng, [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=g, id=f"r{i}")
                for i, (n, g) in enumerate([(5, 6), (21, 4)])])
    per = cfg.expert_step["layers"] * cfg.top_k
    recs = eng.tickprof.tail(256)
    assert any(r["c"]["prefill_tokens"] for r in recs)
    for r in recs:
        c = r["c"]
        assert c["expert_rows_kernel"] == 0
        assert c["expert_rows_ragged"] == per * (
            c["prefill_tokens"] + 3 * ("device" in r["s"]))


def test_a_chunked_prompts_fetches_and_the_flight_account(tiny):
    """The by-kind model with chunks: a piece that is not the last waits
    once (`chunk/fetch`, no array to name), the last piece's fetch and
    the tick's name their arrays, the tick's device counters among
    them; `ensure` and `count` are segments; the four flight counters
    are in every record, in order, and a step's `inflight_us` holds its
    chunk's and its tick's dispatch-to-fetch stretches."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=8,
        prefix_cache=False))
    eng.warmup([8])
    rng = np.random.default_rng(2)
    serve(eng, [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=g, id=f"r{i}")
                for i, (n, g) in enumerate([(5, 6), (29, 4)])])
    recs = eng.tickprof.tail(256)
    keys = {k for r in recs for k in r["s"]}
    assert {"chunk/upload", "chunk/dispatch", "chunk/fetch",
            "chunk/fetch/tokens", "chunk/fetch/finished",
            "admit/fetch/tokens", "admit/fetch/finished",
            "device/fetch/tokens", "device/fetch/finished",
            "device/fetch/counters", "ensure", "count"} <= keys
    # a windowed model has no prefix cache: no lookup, no account
    assert {k for k in keys if k.startswith("admit/blocks/")} == {
        "admit/blocks/alloc_evict", "admit/blocks/reserve"}
    middle = [r for r in recs if "chunk/fetch" in r["s"]
              and "chunk/fetch/tokens" not in r["s"]]
    assert len(middle) == 3                 # 29 = 8 + 8 + 8 + the last 5
    for r in recs:
        c, s = r["c"], r["s"]
        assert c["step_us"] == round(r["total_s"] * 1e6)
        assert 0 <= c["fetch_after_ready_us"] <= c["inflight_us"] \
            <= c["step_us"]
        flights = sum(s.get(f"{p}/dispatch", 0) + s.get(f"{p}/fetch", 0)
                      for p in ("admit", "chunk", "device"))
        assert c["inflight_us"] >= round(1e6 * flights) - 4
        # the stretches between a dispatch and its fetch are a few
        # microseconds of Python (and a collection, should one fall there)
        assert c["inflight_us"] <= 1e6 * flights + 2000 + c["gc_us"]
    snap = eng.tickprof.snapshot(window_s=3600)
    assert snap["inflight"]["step_us"] == sum(
        r["c"]["step_us"] for r in recs)
    assert "ensure" in snap["segments"] and "count" in snap["segments"]


@contextlib.contextmanager
def as_on_a_tpu():
    """The one selector, answering as it does in a process whose backend
    is a TPU: the call's shape decides, and the kernel it chooses runs
    through the interpreter here. No option of the model or the engine
    is involved: there is none."""
    from hyperion_tpu.models import afmoe, llama

    select = llama.select_paged_attn_impl

    def forced(window, rep, backend):
        return select(window, rep, "tpu")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(afmoe, "select_paged_attn_impl", forced)
        mp.setattr(llama, "select_paged_attn_impl", forced)
        yield


def kernel_twin(cfg):
    """The same model under another identity (a longer rotary table,
    which no engine here reaches into): the jits are shared process-wide
    and keyed by the model, so the twin holds traces of its own."""
    return Afmoe(dataclasses.replace(cfg, max_len=80))


def test_the_tick_reads_both_pools_in_place_and_serves_the_same_tokens(tiny):
    """The decode tick through the paged-attention kernel, each layer
    with its kind's table and window, against the tick through the
    gather: the same greedy streams over requests that slide well past
    the window of 8; nothing is gathered a tick (`memory_ledger`), and
    the tick record counts each kind's walk as the tables themselves
    show it."""
    cfg, model, params = tiny
    ecfg = EngineConfig(slots=3, max_len=64, block_size=4, prefill_chunk=16,
                        prefix_cache=False)
    bs, W = 4, cfg.sliding_window

    def requests():
        rng = np.random.default_rng(5)
        return [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=g, id=f"r{i}")
                for i, (n, g) in enumerate(
                    [(5, 30), (37, 20), (3, 44), (20, 12), (9, 3)])]

    gathered = requests()
    eng = Engine(model, {"params": params}, ecfg)
    assert eng._tick_read == "gather"
    serve(eng, gathered)

    checked = []
    with as_on_a_tpu():
        eng = Engine(kernel_twin(cfg), {"params": params}, ecfg)
        assert eng._tick_read == "pallas"
        assert eng.memory_ledger()["kv_gather_bytes_per_tick"] == 0
        # five layers of two kinds: two traces of the kernel, called five
        # times, not a trace and a lowering a layer
        text = eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, eng._rows_on_device(),
            jnp.asarray(eng._live_mask())).as_text()
        assert text.count("func.func private @_paged_attention") == 2
        assert text.count("call @_paged_attention") == 5
        eng.warmup([16])
        compiled = eng.compile_stats()
        tick = eng._tick_device

        def spy():
            # at dispatch the mapped entries of a live row ARE its walk
            live = eng._live_mask()
            masked = int((~live).sum())
            rows = {k: t[live] for k, t in eng._bts.items()}
            n = np.array([q.n_filled
                          for q, on in zip(eng._seqs, live) if on])
            hi = -(-(n + 1) // bs)
            c = eng._walk_counted
            assert c["kv_blocks_walked"] == hi.sum() + masked \
                == np.count_nonzero(rows["full"]) + masked
            assert c["kv_blocks_walked_window"] == masked + \
                (hi - np.maximum(n - W + 1, 0) // bs).sum() \
                == np.count_nonzero(rows["window"]) + masked
            assert c["kv_table_entries"] == 3 * eng._mb == 3 * 16
            assert c["kv_table_entries_window"] == \
                3 * window_view_blocks(W, 1, bs)
            checked.append(
                (c["kv_blocks_walked"], c["kv_blocks_walked_window"]))
            return tick()

        eng._tick_device = spy
        in_place = requests()
        serve(eng, in_place)
        assert eng.compile_stats() == compiled
    for a, b in zip(gathered, in_place):
        assert a.status == b.status == "done"
        assert a.tokens == b.tokens, a.id
    assert len(checked) >= 40
    # slots slid past the window: the windowed walk fell behind the full
    assert any(w < f for f, w in checked)
    recs = [r["c"] for r in eng.tickprof.tail(256) if "device" in r["s"]]
    assert [(c["kv_blocks_walked"], c["kv_blocks_walked_window"])
            for c in recs] == checked[-len(recs):]
    snap = eng.tickprof.snapshot(window_s=3600)["counters"]
    assert snap["kv_blocks_walked_window"] == sum(w for _, w in checked)
    assert snap["kv_table_entries_window"] == 9 * len(checked)


@pytest.mark.parametrize("where", ["off_a_tpu", "as_on_a_tpu"])
def test_the_step_record_counts_the_prompt_positions_by_read(tiny, where):
    """`prompt_positions_tiled` + `prompt_positions_gather` of a step
    are the positions of its prefills and its chunk, bucket padding and
    all (`prefill_tokens`), each window under the read
    `select_paged_attn_impl` names for its width: the question the
    model asked at trace time. Off a TPU every window gathers. With the
    selector answering as on a TPU, and the threshold brought down to
    this engine's chunk of 16 (32 rows), the chunks and the 16-position buckets
    read through the tiled kernel (the interpreter here), windowed
    layers and released blocks and all, the 8-position buckets through
    the gather, and the greedy streams are the gather's."""
    from hyperion_tpu.models import llama
    from hyperion_tpu.obs.tickprof import PROMPT_READ_COUNTERS

    cfg, model, params = tiny
    ecfg = EngineConfig(slots=3, max_len=64, block_size=4, prefill_chunk=16,
                        prefix_cache=False)

    def requests():
        rng = np.random.default_rng(11)
        return [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=g, id=f"r{i}")
                for i, (n, g) in enumerate([(5, 4), (37, 6), (28, 3)])]

    def served(eng, reqs, backend):
        """Serve, and hold the counters to the windows the engine
        dispatched and to the selector's answer for each."""
        windows, count = [], eng._count_prompt
        eng._count_prompt = lambda n: (windows.append(n), count(n))[1]
        serve(eng, reqs)
        recs = eng.tickprof.tail(256)
        assert any("chunk" in r["s"] for r in recs)
        total = eng.exposition()["tickprof"]["counters"]
        assert sum(windows) == total["prefill_tokens"]
        for read in ("tiled", "gather"):
            name = f"prompt_positions_{read}"
            assert total[name] == sum(r["c"][name] for r in recs) == sum(
                n for n in windows
                if llama.select_paged_attn_impl(n, 2, backend) == read)
        assert all(sum(r["c"][k] for k in PROMPT_READ_COUNTERS)
                   <= r["c"]["prefill_tokens"] for r in recs)
        return total

    plain = requests()
    total = served(Engine(model, {"params": params}, ecfg), plain, "cpu")
    # 5 -> 8; 37 -> 16 + 16 + 8; 28 -> 16 + 16: all that was prefilled
    assert total["prompt_positions_gather"] == 8 + 40 + 32 \
        == total["prefill_tokens"]
    assert total["prompt_positions_tiled"] == 0
    if where == "off_a_tpu":
        return
    with as_on_a_tpu(), pytest.MonkeyPatch.context() as mp:
        mp.setattr(llama, "PAGED_TILED_MIN_ROWS", 32)
        # an identity of its own: its traces hold another read
        twin = Afmoe(dataclasses.replace(cfg, max_len=88))
        eng = Engine(twin, {"params": params}, ecfg)
        # two query heads a KV head: a bucket of 8 is 16 rows, which
        # the selector reads in place like a verify window, and the
        # counters of the two gathered reads leave out
        assert [llama.select_paged_attn_impl(t, 2, "tpu")
                for t in (1, 8, 16)] == ["pallas", "pallas", "tiled"]
        tiled = requests()
        total = served(eng, tiled, "tpu")
    assert total["prompt_positions_tiled"] == 32 + 32
    assert total["prompt_positions_gather"] == 0
    assert total["prefill_tokens"] == 64 + 8 + 8
    for a, b in zip(plain, tiled):
        assert a.status == b.status == "done"
        assert a.tokens == b.tokens, a.id


def test_off_a_tpu_the_tick_gathers_and_says_so(tiny):
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=16,
        prefix_cache=False))
    assert eng._tick_read == "gather"
    by = eng._kind_block_bytes
    assert eng.memory_ledger()["kv_gather_bytes_per_tick"] == 3 * (
        16 * by["full"] + 3 * by["window"])
    eng.submit(Request(prompt_ids=np.arange(1, 12, dtype=np.int32),
                       max_new_tokens=6, id="g"))
    serve(eng, [])
    c = [r["c"] for r in eng.tickprof.tail(64) if "device" in r["s"]]
    assert c and all(
        x["kv_blocks_walked"] == x["kv_blocks_walked_window"] == 0
        and x["kv_table_entries"] == 48 and x["kv_table_entries_window"] == 9
        for x in c)


@pytest.mark.parametrize("feature, over", [
    ("prefix cache", dict(prefix_cache=True)),
    ("host spill tier", dict(prefix_cache=False, host_cache_mb=1)),
    ("speculative decoding", dict(prefix_cache=False, spec_k=2,
                                  draft="ngram")),
])
def test_what_cannot_combine_with_a_window_raises(tiny, feature, over):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="windowed layers.*" + feature):
        Engine(model, {"params": params},
               EngineConfig(slots=2, max_len=32, block_size=4, **over))


def test_llama_declares_full_and_takes_tables_either_way():
    """`Llama` keeps every position in every layer: one kind, one pool
    size, and the same program whether the table comes as the engine
    hands it (by kind) or bare."""
    model = Llama(llama_tiny_config(max_len=32, n_kv_heads=2))
    assert set(model.cfg.layer_kinds) == {("full", 0)}
    params = model.init_params(jax.random.key(0), seq=8)
    eng = Engine(model, {"params": params},
                 EngineConfig(slots=2, max_len=32, eos_id=None))
    assert list(eng._mgrs) == ["full"] and eng.mgr is eng._mgrs["full"]
    assert eng._bt is eng._bts["full"]

    def text(tables):
        return eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, tables,
            jnp.asarray(eng._live_mask())).as_text()

    assert text(jnp.asarray(eng._bt)) == text(eng._rows_on_device())


def test_scopes_of_the_tick_say_the_kind_and_the_expert_stage(tiny):
    import re

    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=2, max_len=32, block_size=4, prefill_chunk=8,
        prefix_cache=False))
    text = eng._tick_jit.lower(
        eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
        eng._cache, eng._state, eng._rows_on_device(),
        jnp.asarray(eng._live_mask())).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    scopes = {re.sub(r"layer_\d+", "layer_*", n) for n in names}

    def has(part):
        return any(part in s for s in scopes)

    for kind in ("window", "full"):
        for stage in ("kv_write", "kv_read", "attention", "qk_norm", "gate"):
            assert has(f"Afmoe/layer_*/{kind}/attn/{stage}"), (kind, stage)
    assert has("layer_*/window/attn/rope")
    assert not has("layer_*/full/attn/rope")
    for stage in ("router", "dispatch", "experts", "combine", "shared"):
        assert has(f"Afmoe/layer_*/moe/{stage}"), stage


def test_doctor_row_reads_the_expert_and_kind_counters(tmp_path):
    import json

    from hyperion_tpu.obs import doctor

    tp = {"dominant": "device", "dominant_frac": 0.8, "ticks": 10,
          "window_s": 60.0, "total_s": 1.25,
          "segments": {"device": {"s": 1.0, "frac": 0.8}},
          "counters": {"kv_tokens": 9000, "kv_tokens_window": 4100,
                       "prefill_tokens": 512},
          "experts": {"ticks": 10, "picks_held_per_tick": 47.5,
                      "touched_per_tick": 40.2, "load_max": 5}}
    (tmp_path / "telemetry.jsonl").write_text(json.dumps(
        {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
         "tickprof": tp}) + "\n")
    row = next(ln for ln in doctor.render_markdown(
        doctor.diagnose(tmp_path)).splitlines()
        if ln.startswith("| host tick profile"))
    assert "4100 of them still held by the `window` layers" in row
    assert "47.50 picks a tick on 40.20 held experts" in row
    assert "busiest got 5" in row


@pytest.mark.parametrize("counters, says", [
    # both kinds read in place: the row sums them
    ({"kv_blocks_walked": 4000, "kv_table_entries": 18432,
      "kv_blocks_walked_window": 2400, "kv_table_entries_window": 6168},
     "read in place: 6400 of 24600 table entries"),
    # the ticks gathered
    ({"kv_blocks_walked": 0, "kv_table_entries": 18432,
      "kv_blocks_walked_window": 0, "kv_table_entries_window": 6168},
     "read in place: 0 of 24600 table entries"),
    # a model of one kind: the row is what it was
    ({"kv_blocks_walked": 3137, "kv_table_entries": 6144},
     "read in place: 3137 of 6144 table entries"),
])
def test_doctor_row_sums_the_walk_over_the_kinds(tmp_path, counters, says):
    import json

    from hyperion_tpu.obs import doctor

    tp = {"dominant": "device", "dominant_frac": 0.8, "ticks": 10,
          "window_s": 60.0, "total_s": 1.25,
          "segments": {"device": {"s": 1.0, "frac": 0.8}},
          "counters": {"kv_tokens": 9000, "prefill_tokens": 0, **counters}}
    (tmp_path / "telemetry.jsonl").write_text(json.dumps(
        {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
         "tickprof": tp}) + "\n")
    row = next(ln for ln in doctor.render_markdown(
        doctor.diagnose(tmp_path)).splitlines()
        if ln.startswith("| host tick profile"))
    assert says in row


def test_config_layer_kinds():
    cfg = afmoe_tiny_config(layer_types=(SLIDING, FULL))
    assert cfg.layer_kinds == (("window", 8), ("full", 0))
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(cfg, experts_held=(6, 4))
