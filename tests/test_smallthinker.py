"""`models/smallthinker.py` (PowerInfer SmallThinker's block: a router
that reads the layer's input, softmax weights over the picks, ReLU-gated
experts, NoPE full layers among rotary window layers), the two routers
over the one expert step of `ops/moe.py`, and the serving cache by layer
kind under a second model, against the plain reference
`benchmarks/reference/smallthinker.py`: float32, tiny sizes (two
periods, window 8, contexts to 56, 16-64 experts)."""

from __future__ import annotations

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_harness.smallthinker_faults import (
    EQUATIONS,
    fault as wrong_reference,
)
from benchmarks.reference import smallthinker as reference
from hyperion_tpu.models.llama import init_paged_cache, window_view_blocks
from hyperion_tpu.models.smallthinker import (
    Smallthinker,
    smallthinker_tiny_config,
)
from hyperion_tpu.ops import moe
from hyperion_tpu.ops.moe import grouped_experts, softmax_topk_route
from hyperion_tpu.serve.engine import Engine, EngineConfig
from hyperion_tpu.serve.queue import Request

TOL = 1e-4


def ref_kw(cfg, **over):
    return {**dict(
        sliding=cfg.sliding_window_layout, rotary=cfg.rope_layout,
        window=cfg.sliding_window, theta=cfg.rope_theta, eps=cfg.norm_eps,
        top_k=cfg.top_k), **over}


def make(cfg, seed=1):
    """Weights with every norm scale moved off 1 and every matrix ten
    times its initial size, so that each of them matters (the block has
    no norm on what it adds to the stream: at twenty times the stream
    outgrows float32's rounding)."""
    model = Smallthinker(cfg)
    params = model.init_params(jax.random.key(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    leaves = [x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
              if x.ndim == 1 else 10 * x for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


@pytest.fixture(scope="module")
def tiny():
    cfg = smallthinker_tiny_config()
    return (cfg, *make(cfg))


def ids_of(cfg, n, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, n), 1,
                              cfg.vocab_size)


# ------------------------------------------------------------- the model


def test_config_is_the_published_layout():
    cfg = smallthinker_tiny_config()
    # the period STARTS with its full layer, twice over
    assert cfg.layer_kinds == (
        ("full", 0), ("window", 8), ("window", 8), ("window", 8)) * 2
    assert cfg.rope_layout == cfg.sliding_window_layout
    assert cfg.n_heads // cfg.n_kv_heads == 7
    with pytest.raises(ValueError, match="rope_layout"):
        dataclasses.replace(cfg, rope_layout=(0, 1))


@pytest.mark.parametrize("batch, length", [
    (2, 40),    # five windows long
    (3, 8),     # no key has left the window yet: both kinds see the same
    (1, 64),    # the whole of `max_len`
])
def test_model_agrees_with_the_plain_reference(tiny, batch, length):
    cfg, model, params = tiny
    ids = ids_of(cfg, length, batch=batch)
    got = model.apply({"params": params}, ids)
    want = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(want.std()) > 0.5     # not a comparison of zeros
    # the reference's head on some rows is the rows of its whole answer
    some = reference.logits(params, ids, **ref_kw(cfg), rows=(3, 5))
    assert float(jnp.abs(some - want[:, 3:8]).max()) < 1e-5


@pytest.mark.parametrize("fault", EQUATIONS)
def test_each_fault_fails_the_comparison(tiny, fault):
    """What the comparison has to see: a router fed the normed input or
    the post-attention state (what `afmoe` routes on), SiLU for ReLU,
    sigmoid weights for the softmax, the window mask, rotary positions
    kept off the full layers and on the sliding ones each move the
    logits far past the tolerance."""
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
    position's logits against the reference's one full forward."""
    cfg, model, params = tiny
    bs, P, total = 4, 37, 56
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
    # the full layers do read those blocks
    lost = {**tables, "full": tables["full"].at[:, :gone].set(0)}
    other, _ = model.apply(v, ids[:, P:], block_tables=lost, **kw)
    assert float(jnp.abs(whole - other).max()) > 100 * TOL


# ------------------------------------- two routers over one expert step


def expert_params(key, d=16, f=8, experts=64):
    k = jax.random.split(key, 4)
    return {"router": jax.random.normal(k[0], (d, experts)),
            "gate": jax.random.normal(k[1], (experts, d, f)) / 4,
            "up": jax.random.normal(k[2], (experts, d, f)) / 4,
            "down": jax.random.normal(k[3], (experts, f, d)) / 4}


def share(p, first, count):
    return {**p, **{k: p[k][first:first + count]
                    for k in ("gate", "up", "down")}}


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """The routed parts of the four chips that would divide a layer's 64
    experts add up to what one chip holding all of them gives, and to
    the plain form: every expert on every token, weighted by the picks
    made on ANOTHER input than the one the experts read."""
    p = expert_params(jax.random.key(0))
    h = jax.random.normal(jax.random.key(1), (24, 16))   # the router's
    x = jax.random.normal(jax.random.key(2), (24, 16))   # the experts'
    picked, w = softmax_topk_route(h, p["router"], top_k=6)
    whole, load = grouped_experts(x, picked, w, p, held=(0, 64),
                                  act=jax.nn.relu)
    parts = [grouped_experts(x, picked, w, share(p, first, 16),
                             held=(first, 16), act=jax.nn.relu)
             for first in (0, 16, 32, 48)]
    assert float(jnp.abs(sum(y for y, _ in parts) - whole).max()) < 1e-5
    assert int(load.sum()) == 24 * 6
    assert sum(int(ld.sum()) for _, ld in parts) == 24 * 6
    want = reference.experts(
        x, h @ p["router"],
        {"experts_gate": p["gate"], "experts_up": p["up"],
         "experts_down": p["down"]}, top_k=6)
    assert float(jnp.abs(whole - want).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_no_token_is_dropped_when_every_row_picks_the_same_six_experts():
    """No capacity: 40 rows that all pick experts 3-8 all get all six
    outputs (a capacity layer at factor 1.25 would serve 5 rows an
    expert of the 40 that ask)."""
    p = expert_params(jax.random.key(2), experts=64)
    # one input direction that only experts 3..8 answer to
    router = jnp.zeros((16, 64)).at[0, 3:9].set(
        jnp.asarray([3.0, 2.5, 2.0, 1.5, 1.0, 0.5]))
    x = jax.random.normal(jax.random.key(3), (40, 16))
    h = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    picked, w = softmax_topk_route(h, router, top_k=6)
    assert bool((jnp.sort(picked, -1) == jnp.arange(3, 9)).all())
    y, load = grouped_experts(x, picked, w, p, held=(0, 64),
                              act=jax.nn.relu)
    assert bool((load[:, 3:9] == 1).all()) and int(load.sum()) == 40 * 6
    want = sum(
        jax.nn.softmax(h @ router[:, 3:9], -1)[:, e - 3:e - 2]
        * ((jax.nn.relu(x @ p["gate"][e]) * (x @ p["up"][e])) @ p["down"][e])
        for e in range(3, 9))
    assert float(jnp.abs(y - want).max()) < 1e-5
    assert float(jnp.abs(y).max(axis=-1).min()) > 0    # every row served


@pytest.mark.parametrize("case", [
    "softmax_over_the_picks", "float32_logits", "another_activation",
    "afmoe_composes_the_same_step"])
def test_routers_and_the_expert_step(case):
    x = jax.random.normal(jax.random.key(4), (12, 16))
    p = expert_params(jax.random.key(5), experts=8)
    picked, w = softmax_topk_route(x, p["router"], top_k=3)
    if case == "softmax_over_the_picks":
        # the softmax over all experts, renormalised over the picks
        probs = jax.nn.softmax(x @ p["router"], -1)
        top, idx = jax.lax.top_k(probs, 3)
        assert bool((idx == picked).all()) and picked.dtype == jnp.int32
        assert jnp.allclose(w, top / top.sum(-1, keepdims=True), atol=1e-6)
        assert jnp.allclose(w.sum(-1), 1.0, atol=1e-6)
    elif case == "float32_logits":
        _, w16 = softmax_topk_route(
            x.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16),
            top_k=3)
        assert w16.dtype == jnp.float32
        assert bool((w16 != w16.astype(jnp.bfloat16)).any())
    elif case == "another_activation":
        relu, _ = grouped_experts(x, picked, w, p, held=(0, 8),
                                  act=jax.nn.relu)
        silu, _ = grouped_experts(x, picked, w, p, held=(0, 8))
        assert float(jnp.abs(relu - silu).max()) > 1e-2
    else:
        # `dropless_moe` is the sigmoid router, then the same step
        pb = {**p, "expert_bias": jnp.zeros((8,))}
        kw = dict(top_k=3, route_norm=True, route_scale=2.448)
        y, load = moe.dropless_moe(x, share(pb, 2, 4), held=(2, 4), **kw)
        pk, ws = moe.sigmoid_topk_route(
            x, p["router"], pb["expert_bias"], **kw)
        y2, load2 = grouped_experts(x, pk, ws, share(p, 2, 4), held=(2, 4))
        assert float(jnp.abs(y - y2).max()) == 0.0
        assert bool((load == load2).all())


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
        for q in eng._allocs["window"]:
            assert q is None or len(q.blocks) <= eng._hold["window"]
        peak = max(peak, win.in_use)
    return peak


def requests(cfg, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g, id=f"r{i}")
            for i, (n, g) in enumerate(shapes)]


ENGINE = dict(slots=3, max_len=64, block_size=4, prefill_chunk=8,
              prefix_cache=False)


@pytest.mark.parametrize("admission", ["reserve", "optimistic"])
def test_engine_serves_short_and_long_requests_in_one_queue(tiny, admission):
    """The second model to declare layer kinds, through the same jits
    and block managers: a period that BEGINS with the full kind, no
    dense layer, every expert held. Served tokens are the reference's
    greedy tokens, the windowed pool stays under its bound, and the
    tick record counts every pick: rows x 6 x layers."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params},
                 EngineConfig(**ENGINE, admission=admission))
    assert list(eng._kinds) == ["full", "window"]
    assert eng._hold == {"full": 16, "window": 6}
    assert {k: m.num_blocks for k, m in eng._mgrs.items()} == \
        {"full": 49, "window": 19}
    eng.warmup([8])
    compiled = eng.compile_stats()
    reqs = requests(cfg, [(5, 6), (30, 20), (44, 12), (7, 3), (21, 30),
                          (3, 40)])
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
    recs = [r for r in eng.tickprof.tail(256) if "device" in r["s"]]
    c = [r["c"] for r in recs]
    assert all({"kv_tokens", "kv_tokens_window", "expert_picks_held",
                "experts_touched", "expert_load_max"} <= set(x) for x in c)
    assert any(x["kv_tokens_window"] < x["kv_tokens"] for x in c)
    # every expert is held: no pick lands elsewhere
    assert cfg.top_k == 6 and cfg.n_layers == 8
    assert {x["expert_picks_held"] % (6 * 8) for x in c} == {0}
    assert {x["expert_picks_held"] // (6 * 8) for x in c} <= {1, 2, 3}
    assert all(x["experts_touched"] <= min(
        x["expert_picks_held"], 8 * 16) for x in c)
    ledger = eng.memory_ledger()
    assert set(ledger["kv_by_kind"]) == {"full", "window"}


def test_a_windowed_chain_lets_blocks_go_within_the_step(tiny):
    cfg, model, params = tiny
    eng = Engine(model, {"params": params},
                 EngineConfig(**{**ENGINE, "slots": 1}))
    eng.submit(Request(prompt_ids=np.arange(1, 41, dtype=np.int32),
                       max_new_tokens=20, id="long"))
    while not eng.idle:
        eng.step()
        full, win = eng._seqs[0], eng._allocs["window"][0]
        if win is None:
            continue
        gone = max(0, full.n_filled - cfg.sliding_window + 1) // 4
        assert win.first == gone
        assert eng._mgrs["window"].in_use == len(win.blocks)
        assert len(full.blocks) >= -(-full.n_filled // 4)   # full keeps all


@contextlib.contextmanager
def as_on_a_tpu():
    """The one selector, answering as it does in a process whose backend
    is a TPU (`tests/test_afmoe.py` has the same for `afmoe`): the
    kernel it chooses runs through the interpreter here."""
    from hyperion_tpu.models import llama, smallthinker

    select = llama.select_paged_attn_impl

    def forced(window, rep, backend):
        return select(window, rep, "tpu")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(smallthinker, "select_paged_attn_impl", forced)
        mp.setattr(llama, "select_paged_attn_impl", forced)
        yield


def test_the_tick_reads_both_pools_in_place_at_seven_rows_a_kv_head(tiny):
    """The decode tick through the paged-attention kernel (7 query rows
    a KV head, padded to 8; the full kind's table first) against the
    tick through the gather: the same greedy streams over requests that
    slide past the window."""
    cfg, model, params = tiny
    ecfg = EngineConfig(**{**ENGINE, "prefill_chunk": 16})
    shapes = [(5, 24), (37, 14), (3, 30), (20, 8)]
    gathered = requests(cfg, shapes, seed=5)
    eng = Engine(model, {"params": params}, ecfg)
    assert eng._tick_read == "gather"
    serve(eng, gathered)
    with as_on_a_tpu():
        # another identity: the jits are shared process-wide and keyed
        # by the model
        twin = Smallthinker(dataclasses.replace(cfg, max_len=80))
        eng = Engine(twin, {"params": params}, ecfg)
        assert eng._tick_read == "pallas"
        assert eng.memory_ledger()["kv_gather_bytes_per_tick"] == 0
        text = lowered(eng, "tick")
        # eight layers of two kinds: two traces of the kernel
        assert text.count("func.func private @_paged_attention") == 2
        assert text.count("call @_paged_attention") == 8
        in_place = requests(cfg, shapes, seed=5)
        serve(eng, in_place)
    for a, b in zip(gathered, in_place):
        assert a.status == b.status == "done"
        assert a.tokens == b.tokens, a.id
    c = [r["c"] for r in eng.tickprof.tail(256) if "device" in r["s"]]
    assert any(0 < x["kv_blocks_walked_window"] < x["kv_blocks_walked"]
               for x in c)
    assert all(x["kv_table_entries_window"]
               == 3 * window_view_blocks(cfg.sliding_window, 1, 4)
               for x in c)


@pytest.mark.parametrize("where", ["off_a_tpu", "the_tick_on_the_kernel"])
def test_the_tick_record_counts_the_expert_rows_by_form(tiny, where):
    """`expert_rows_kernel` + `expert_rows_ragged` of a step are
    expert layers x top_k x the positions its programs ran (the tick's
    slots, live or not; a chunk; a prefill's bucket), each under the
    form `select_grouped_impl` names for that call's rows: the question
    `grouped_experts` asked at trace time. Off a TPU every row goes
    through `ragged_dot`; with a selector that sends the tick's shape
    to the kernel (through the interpreter here) the tick's rows move
    over, the tokens stay the same, and a layer's three products share
    one trace of the kernel."""
    cfg, model, params = tiny
    ecfg = EngineConfig(**ENGINE)
    shapes = [(5, 9), (30, 6), (3, 12), (19, 5)]
    per = cfg.n_layers * cfg.top_k
    tick_rows = ecfg.slots * cfg.top_k
    assert cfg.expert_step == {
        "layers": 8, "groups": 16, "top_k": 6, "k": 32, "n": 16,
        "itemsize": 4}

    def checked(eng, form_of_the_tick):
        recs = eng.tickprof.tail(256)
        assert any("chunk" in r["s"] for r in recs)
        for r in recs:
            c, ticked = r["c"], "device" in r["s"]
            want = {"expert_rows_kernel": 0, "expert_rows_ragged":
                    per * c["prefill_tokens"]}
            want[f"expert_rows_{form_of_the_tick}"] += \
                per * ecfg.slots * ticked
            assert {k: c[k] for k in want} == want, r
        total = eng.exposition()["tickprof"]["counters"]
        assert total["expert_rows_kernel"] + total["expert_rows_ragged"] \
            == sum(r["c"]["expert_rows_kernel"] + r["c"]["expert_rows_ragged"]
                   for r in recs)

    plain = requests(cfg, shapes, seed=7)
    eng = Engine(model, {"params": params}, ecfg)
    serve(eng, plain)
    checked(eng, "ragged")
    if where == "off_a_tpu":
        assert "grouped_matmul" not in lowered(eng, "tick")
        return

    def the_tick_only(rows, groups, k, n, backend, itemsize=2):
        assert (groups, k, n, itemsize) == (16, 32, 16, 4)
        return "kernel" if rows == tick_rows else "ragged"

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "select_grouped_impl", the_tick_only)
        # another identity: the jits are shared process-wide
        twin = Smallthinker(dataclasses.replace(cfg, max_len=72))
        eng = Engine(twin, {"params": params}, ecfg)
        text = lowered(eng, "tick")
        # eight layers, three products each, one trace of the kernel for
        # each orientation
        assert text.count("func.func private @_grouped_matmul") == 2
        assert text.count("call @_grouped_matmul") == 24
        assert "grouped_matmul" not in lowered(eng, "chunk")
        through_the_kernel = requests(cfg, shapes, seed=7)
        serve(eng, through_the_kernel)
    checked(eng, "kernel")
    for a, b in zip(plain, through_the_kernel):
        assert a.status == b.status == "done"
        assert a.tokens == b.tokens, a.id


@pytest.mark.parametrize("feature, over", [
    ("prefix cache", dict(prefix_cache=True)),
    ("host spill tier", dict(prefix_cache=False, host_cache_mb=1)),
    ("speculative decoding", dict(prefix_cache=False, spec_k=2,
                                  draft="ngram")),
])
def test_what_cannot_combine_with_a_window_raises(tiny, feature, over):
    cfg, model, params = tiny
    with pytest.raises(ValueError,
                       match="Smallthinker has windowed layers.*" + feature):
        Engine(model, {"params": params},
               EngineConfig(slots=2, max_len=32, block_size=4, **over))


def lowered(eng, which: str) -> str:
    """The lowered text of one of the engine's three programs at its
    own shapes (no locations: `as_text()` leaves them out)."""
    bt_row = {k: jnp.zeros((eng._mb,), jnp.int32) for k in eng._kinds}
    if which == "tick":
        return eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, eng._rows_on_device(),
            jnp.asarray(eng._live_mask())).as_text()
    if which == "chunk":
        return eng._chunk_jit.lower(
            eng.model, eng.variables, eng._cache,
            jnp.zeros((1, eng.cfg.prefill_chunk), jnp.int32), bt_row,
            jnp.int32(0)).as_text()
    return eng._prefill_jit.lower(
        eng.model, eng.cfg.eos_id, eng.variables, eng._cache, eng._state,
        jnp.zeros((1, 8), jnp.int32), bt_row, jnp.int32(0), jnp.int32(0),
        jnp.int32(5), jnp.float32(0), jnp.int32(0), jnp.float32(1),
        jnp.int32(4), jax.random.key(0)).as_text()


def test_scopes_of_the_tick_say_the_kind_and_the_expert_stage(tiny):
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(**ENGINE))
    text = eng._tick_jit.lower(
        eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
        eng._cache, eng._state, eng._rows_on_device(),
        jnp.asarray(eng._live_mask())).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    scopes = {re.sub(r"layer_\d+", "layer_*", n) for n in names}

    def has(part):
        return any(part in s for s in scopes)

    for kind in ("window", "full"):
        for stage in ("qkv_proj", "kv_write", "kv_read", "attention",
                      "o_proj"):
            assert has(f"Smallthinker/layer_*/{kind}/attn/{stage}"), \
                (kind, stage)
    assert has("layer_*/window/attn/rope")
    assert not has("layer_*/full/attn/rope")
    # the router is the layer's own, outside the kind and the experts
    assert has("Smallthinker/layer_*/router")
    assert not has("layer_*/moe/router")
    for stage in ("dispatch", "experts", "combine"):
        assert has(f"Smallthinker/layer_*/moe/{stage}"), stage


def _parent_dropless_moe(x, params, *, held, top_k, route_norm=True,
                         route_scale=1.0):
    """`ops/moe.py` `dropless_moe` as it stood before PR 31 took it
    apart, routing and expert step in one body: what `afmoe`'s programs
    were lowered from."""
    from jax import lax

    first, count = held
    N = x.shape[0]
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), params["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, picked = lax.top_k(
            scores + params["expert_bias"].astype(jnp.float32), top_k)
        w = jnp.take_along_axis(scores, picked, axis=-1)
        if route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        picked, w = picked.astype(jnp.int32), w * route_scale
    with jax.named_scope("dispatch"):
        local = picked - first
        here = (local >= 0) & (local < count)
        flat = jnp.where(here, local, count).reshape(-1)
        order = jnp.argsort(flat, stable=True)
        load = jnp.sum(jax.nn.one_hot(
            jnp.where(here, local, -1), count, dtype=jnp.int32), axis=1)
        sizes = jnp.sum(load, axis=0)
    with jax.named_scope("experts"):
        xs = x[order // top_k]
        gate = lax.ragged_dot(xs, params["gate"], sizes)
        up = lax.ragged_dot(xs, params["up"], sizes)
        ys = lax.ragged_dot(jax.nn.silu(gate) * up, params["down"], sizes)
    with jax.named_scope("combine"):
        back = jnp.argsort(order)
        ys = ys[back].reshape(N, top_k, -1).astype(jnp.float32)
        y = jnp.sum(jnp.where(here[..., None], ys * w[..., None], 0.0), axis=1)
    return y.astype(x.dtype), load


@pytest.mark.parametrize("program", ["tick", "chunk", "prefill"])
def test_trinitys_lowered_programs_are_the_parents_text(program):
    """Taking `dropless_moe` apart changed nothing `afmoe` runs: the
    tiny model's three programs lower to the same text from the router
    and the expert step composed as from the one body the parent had."""
    from hyperion_tpu.models import afmoe

    class AfmoeTwin(afmoe.Afmoe):
        """The same model under another identity: the jits are shared
        process-wide and keyed by the model, so the twin is traced
        afresh."""

    def text(cls):
        model = cls(afmoe.afmoe_tiny_config(experts_held=(2, 4)))
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
                lambda: model.init_params(jax.random.key(0))))
        return lowered(Engine(model, {"params": params},
                              EngineConfig(**ENGINE)), program)

    now = text(afmoe.Afmoe)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(afmoe, "dropless_moe", _parent_dropless_moe)
        parent = text(AfmoeTwin)
    # the expert layer is in the text (the router's pick), and the text
    # is the parent's
    assert "chlo.top_k" in now and now == parent
