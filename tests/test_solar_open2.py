"""`models/solar_open2.py` (Upstage Solar Open 2: Kimi Delta Attention
layers with a recurrent state beside a gated NoPE GQA layer, experts in
every layer) and the engine's `recurrent` layer kind (a state row a
slot beside the paged pool), against the plain reference
`benchmarks/reference/solar_open2.py`: float32, tiny sizes (one period
of four layers, contexts to 60). The engine tests share one engine
shape, so they share its compiled programs."""

from __future__ import annotations

import contextlib
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe as afmoe_reference
from benchmarks.reference import solar_open2 as reference
from hyperion_tpu.models import solar_open2
from hyperion_tpu.models.afmoe import AfmoeMoE
from hyperion_tpu.models.llama import (
    init_paged_cache,
    paged_cache_block_bytes,
    recurrent_state_bytes,
)
from hyperion_tpu.models.solar_open2 import (
    FULL,
    RECURRENT,
    SolarOpen2,
    chunked_delta_rule,
    delta_rule_step,
    solar_open2_tiny_config,
)
from hyperion_tpu.serve.engine import Engine, EngineConfig
from hyperion_tpu.serve.queue import Request

TOL = 1e-4
ENGINE = dict(slots=3, max_len=64, block_size=4, prefill_chunk=8,
              prefix_cache=False)


def ref_kw(cfg):
    return dict(layer_types=cfg.layer_types, theta=10000.0, eps=cfg.norm_eps,
                held=cfg.experts_held, top_k=cfg.top_k)


def make(cfg, seed=1):
    """Weights with every vector moved off its initial value and every
    matrix ten times its initial size, so that each of them matters; the
    decays span weak to strong (A_log -1..2)."""
    model = SolarOpen2(cfg)
    params = jax.jit(lambda key: model.init_params(key, seq=1))(
        jax.random.key(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))

    def moved(path, x, k):
        name = path[-1].key
        if name == "A_log":
            return jax.random.uniform(k, x.shape, x.dtype, -1.0, 2.0)
        if name == "conv":
            return jax.random.uniform(k, x.shape, x.dtype, -0.5, 0.5)
        if x.ndim == 1:
            return x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
        return 10 * x

    leaves = [moved(path, x, k) for (path, x), k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


@pytest.fixture(scope="module")
def tiny():
    cfg = solar_open2_tiny_config(experts_held=(2, 4))
    return (cfg, *make(cfg))


def ids_of(cfg, n, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, n), 1,
                              cfg.vocab_size)


# every comparison with the reference is at this shape (a causal model:
# what follows a row changes nothing before it), so it compiles once
REF_SHAPE = (2, 64)


def ref_logits(cfg, params, rows):
    """The reference's logits of up to two sequences, each padded to
    `REF_SHAPE`."""
    ids = np.ones(REF_SHAPE, np.int32)
    for b, seq in enumerate(rows):
        ids[b, :len(seq)] = seq
    return np.asarray(reference.logits(params, jnp.asarray(ids),
                                       **ref_kw(cfg)))


# ------------------------------------------------------------- the model


def test_config_is_the_published_layout():
    cfg = solar_open2_tiny_config()
    assert cfg.layer_types == (FULL, RECURRENT, RECURRENT, RECURRENT)
    assert cfg.layer_kinds == ((FULL, 0),) + ((RECURRENT, 0),) * 3
    assert cfg.recurrent_state == {"S": ((2, 8, 8), "float32"),
                                   "conv": ((3, 48), "float32")}
    assert cfg.expert_step["layers"] == 4
    # a recurrent layer holds no block, and one slot's rows are its S
    # and its conv tail
    assert paged_cache_block_bytes(cfg, 4) == 2 * 1 * 4 * 2 * 8 * 4
    assert recurrent_state_bytes(cfg) == 3 * (2 * 8 * 8 + 3 * 48) * 4
    with pytest.raises(ValueError, match="unknown layer types"):
        solar_open2_tiny_config(layer_types=("full", "window"))


def test_model_agrees_with_the_plain_reference(tiny):
    """One full forward (two chunks of 32) against the token-by-token
    reference."""
    cfg, model, params = tiny
    ids = ids_of(cfg, REF_SHAPE[1], batch=REF_SHAPE[0])
    got = jax.jit(model.apply)({"params": params}, ids)
    want = ref_logits(cfg, params, np.asarray(ids))
    assert float(want.std()) > 0.3
    assert float(jnp.abs(got - want).max()) < TOL * max(1.0, float(
        want.std()))


@pytest.mark.parametrize("decay", [0.01, 20.0], ids=["weak", "strong"])
def test_the_chunked_scan_is_the_step_recurrence(decay):
    """Chunks of 16 over 70 positions from a nonzero state against 70
    steps, at log-decays down to -`decay` a step and beta near 2 (the
    update's eigenvalue near -1): nothing overflows, both agree."""
    ks = jax.random.split(jax.random.key(2), 6)
    B, T, H, K = 2, 70, 3, 8

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (B, T, H, K)))
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, K))
    g = -decay * jax.random.uniform(ks[3], (B, T, H, K))
    beta = 2 * jax.nn.sigmoid(7 + 0.5 * jax.random.normal(ks[4], (B, T, H)))
    assert float(beta.min()) > 1.98
    S = jax.random.normal(ks[5], (B, H, K, K))
    o, S1 = jax.jit(chunked_delta_rule, static_argnames="chunk")(
        q, k, v, g, beta, S, chunk=16)
    S, want = jax.jit(lambda S, xs: jax.lax.scan(
        lambda S, x: delta_rule_step(*x, S)[::-1], S, xs))(
        S, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    want = jnp.moveaxis(want, 0, 1)
    assert bool(jnp.isfinite(o).all() and jnp.isfinite(S1).all())
    scale = max(1.0, float(jnp.abs(want).max()))
    assert float(jnp.abs(o - want).max()) < 1e-5 * scale
    assert float(jnp.abs(S1 - S).max()) < 1e-5 * max(
        1.0, float(jnp.abs(S).max()))


def test_paged_prefill_then_decode_agrees_with_the_full_forward(tiny):
    """Two slots of the engine's cache layout: slot 1's prompt of 21 in
    a bucket of 32 (its pad positions must not enter the state), then in
    chunks of 8 for slot 2 (a chunk boundary inside the prompt), then
    ticks over all three rows where slot 0 is idle (`state_len` 0) and
    keeps its rows bit for bit; every position's logits against the
    reference's one full forward."""
    cfg, model, params = tiny
    bs, P, total, MB = 4, 21, 40, 16
    ids = np.asarray(ids_of(cfg, total, seed=5, batch=2))
    want = ref_logits(cfg, params, ids)
    v = {"params": params}
    apply = jax.jit(model.apply)
    cache = init_paged_cache(cfg, 3 * MB + 1, bs, slots=3)
    cache[1]["S"] = cache[1]["S"].at[0].set(7.0)       # slot 0's own state
    tables = jnp.asarray(np.arange(1, 3 * MB + 1).reshape(3, MB), jnp.int32)
    got = {1: [], 2: []}
    bucket = np.zeros((1, 32), np.int32)
    bucket[0, :P] = ids[0, :P]
    out, cache = apply(
        v, jnp.asarray(bucket), cache=cache, cache_index=jnp.int32(0),
        block_tables={FULL: tables[1:2], RECURRENT: jnp.asarray([1])},
        state_len=jnp.asarray([P]))
    got[1].append(out[0, :P])
    for start in range(0, 24, 8):
        piece = ids[1:2, start:start + 8]
        out, cache = apply(
            v, jnp.asarray(piece), cache=cache, cache_index=jnp.int32(start),
            block_tables={FULL: tables[2:3], RECURRENT: jnp.asarray([2])})
        got[2].append(out[0])
    idle = [{k: np.asarray(layer[k][0]) for k in ("S", "conv")}
            for layer in cache[1:]]
    for p in range(24, total):
        toks = jnp.asarray([0, ids[0, p - 3], ids[1, p]], jnp.int32)
        # slot 1 runs three positions behind slot 2 so both decode
        pos = jnp.asarray([0, p - 3, p], jnp.int32)
        out, cache = apply(
            v, toks[:, None], cache=cache, cache_index=pos,
            block_tables={FULL: tables}, state_len=jnp.asarray([0, 1, 1]))
        got[1].append(out[1])
        got[2].append(out[2])
    assert float(idle[0]["S"].min()) == 7.0
    for layer, before in zip(cache[1:], idle):
        for name in ("S", "conv"):
            np.testing.assert_array_equal(np.asarray(layer[name][0]),
                                          before[name])
    for row, n in ((1, total - 3), (2, total)):
        g = jnp.concatenate(got[row])
        w = want[row - 1, :n]
        assert g.shape == w.shape, row
        assert float(jnp.abs(g - w).max()) < TOL * max(1.0, float(w.std()))


def share(p, first, count):
    return {**p, **{k: p[k][first:first + count]
                    for k in ("experts_gate", "experts_up", "experts_down")}}


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The expert layer as the model has it, cut in eight shares of one
    expert each (what the eight chips that divide a layer compute, each
    with the whole shared expert): their routed parts, the shared expert
    counted once, add up to the reference's uncut layer."""
    cfg, _, params = tiny
    p = params["layer_1"]["moe"]
    uncut = solar_open2_tiny_config()      # 8 experts, all held
    d, f = cfg.d_model, cfg.moe_ff_dim
    full = {**p, **{k: 0.2 * jax.random.normal(jax.random.key(i), shape)
                    for i, (k, shape) in enumerate((
                        ("experts_gate", (8, d, f)),
                        ("experts_up", (8, d, f)),
                        ("experts_down", (8, f, d))))}}
    x = jax.random.normal(jax.random.key(4), (2, 12, cfg.d_model))

    @jax.jit
    def shares(full, x):
        parts = [AfmoeMoE(solar_open2_tiny_config(experts_held=(e, 1)))
                 .apply({"params": share(full, e, 1)}, x) for e in range(8)]
        shared = AfmoeMoE(uncut).apply(
            {"params": {**full, **{k: jnp.zeros_like(full[k]) for k in (
                "experts_gate", "experts_up", "experts_down")}}}, x)
        return sum(parts) - 7 * shared, shared

    got, shared = shares(full, x)
    with jax.default_matmul_precision("highest"):
        want = afmoe_reference.moe(x, full, held=(0, 8), top_k=cfg.top_k,
                                   route_norm=True, route_scale=1.0)
    assert float(jnp.abs(shared).max()) > 0.01
    assert float(jnp.abs(got - want).max()) < 1e-4


# ------------------------------------------------------------ the engine


def drain(eng, until=None):
    while not eng.idle:
        eng.step()
        if until is not None and until():
            return


def requests(cfg, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g, id=f"r{i}")
            for i, (n, g) in enumerate(shapes)]


def assert_served_at_reference_best(cfg, params, reqs):
    """Each served token's reference logit lies within the tolerance of
    its row's best: the logits agree (a near-tie may pick either)."""
    for pair in (reqs[i:i + 2] for i in range(0, len(reqs), 2)):
        seqs = [np.concatenate([r.prompt_ids, np.asarray(r.tokens, np.int32)])
                for r in pair]
        want = ref_logits(cfg, params, seqs)
        for r, rows in zip(pair, want):
            assert r.status == "done" and len(r.tokens) == r.max_new_tokens
            P, n = len(r.prompt_ids), len(r.tokens)
            rows = rows[P - 1:P - 1 + n]
            short = rows.max(-1) - rows[np.arange(n), r.tokens]
            assert short.max() < 2 * TOL * max(1.0, float(rows.std())), r.id


@pytest.fixture
def engine(tiny):
    """An engine of the tests' one shape: every engine here shares the
    compiled programs (the jits are process-wide, keyed by model and
    shapes)."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(**ENGINE))
    eng.warmup([8])
    return eng


def test_engine_serves_short_and_long_requests_in_one_queue(tiny, engine):
    """Prompts in the bucket of 8 (pad positions), prompts that chunk
    (boundaries of 8), admitted while other slots decode: every served
    token's reference logit is its row's best, no program compiles
    after warm-up, the tick record counts the recurrent rows it
    advanced, and the ledger counts the state by slot."""
    cfg, _, params = tiny
    eng = engine
    compiled = eng.compile_stats()
    reqs = requests(cfg, [(5, 6), (30, 14), (19, 9), (3, 20), (27, 5),
                          (8, 12)])
    for r in reqs:
        eng.submit(r)
    drain(eng)
    assert eng.compile_stats() == compiled
    assert eng.mgr.in_use == 0 and eng.mgr.reserved == 0
    assert_served_at_reference_best(cfg, params, reqs)
    ticks = [r for r in eng.tickprof.tail(256) if "device" in r["s"]]
    # rows the tick advanced, a recurrent layer each: live rows x 3
    assert all(r["c"]["recurrent_rows"] % 3 == 0 for r in ticks)
    assert max(r["c"]["recurrent_rows"] for r in ticks) == 3 * 3
    assert {"expert_picks_held", "experts_touched"} <= set(ticks[0]["c"])
    ledger = eng.memory_ledger()
    slot = (2 * 8 * 8 + 3 * 48) * 4 * 3
    assert ledger["state"] == {"slot_bytes": slot, "pool_bytes": 3 * slot,
                               "in_use_bytes": 0}
    assert set(ledger["kv_by_kind"]) == {FULL}
    assert ledger["kv_bytes_per_token"] == 2 * 2 * 8 * 4


def test_a_chunking_slots_state_is_untouched_by_the_tick(tiny, engine):
    """While a long prompt prefills in chunks, the decode tick runs the
    other slots: the chunking slot's rows (and a free slot's) leave the
    tick as they entered it, bit for bit, and the occupied slots count
    in the ledger."""
    cfg, _, _ = tiny
    eng = engine
    short, long = requests(cfg, [(4, 30), (40, 3)], seed=4)
    eng.submit(short)
    drain(eng, until=lambda: len(short.tokens) >= 2)
    eng.submit(long)
    drain(eng, until=lambda: bool(eng._chunking))
    (slot,) = eng._chunking
    assert eng.memory_ledger()["state"]["in_use_bytes"] == \
        2 * eng._slot_state_bytes
    free = next(s for s, r in enumerate(eng._slots) if r is None)
    before = [{k: np.asarray(layer[k]) for k in ("S", "conv")}
              for layer in eng._cache[1:]]
    eng._tick_device()
    for layer, was in zip(eng._cache[1:], before):
        for k in ("S", "conv"):
            for s in (slot, free):
                np.testing.assert_array_equal(np.asarray(layer[k][s]),
                                              was[k][s])
    assert eng._tick_counted["recurrent_rows"] == 3      # the short one


def test_preemption_then_resume_is_an_uninterrupted_run(tiny, engine):
    """A slot preempted mid-decode drops its state rows; the resume
    re-prefills prompt and generated tokens (in chunks of 8), rebuilds
    the state, and serves what an uninterrupted run serves."""
    cfg, _, _ = tiny
    eng = engine
    plain = requests(cfg, [(13, 16)], seed=9)[0]
    eng.submit(plain)
    drain(eng)
    again = requests(cfg, [(13, 16)], seed=9)[0]
    eng.submit(again)
    drain(eng, until=lambda: len(again.tokens) >= 7)
    slot = next(s for s, r in enumerate(eng._slots) if r is again)
    eng._preempt(slot, reason="test")
    drain(eng)
    assert again.preempts == 1
    assert again.tokens == plain.tokens


@pytest.mark.parametrize("feature, over", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("host_cache_mb", dict(host_cache_mb=8)),
    ("spec_k, draft", dict(spec_k=2, draft="ngram")),
])
def test_what_cannot_combine_with_a_recurrent_state_raises(tiny, feature,
                                                           over):
    cfg, model, params = tiny
    kw = {**ENGINE, **over}
    with pytest.raises(ValueError, match="SolarOpen2 has 3 recurrent "
                       r"layers.*\(" + feature + r"\).*Turn it off"):
        Engine(model, {"params": params}, EngineConfig(**kw))


# ----------------------------------------------------------- the programs


def lowered(eng, which: str) -> str:
    """The lowered text of one of the engine's three programs at its
    own shapes."""
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


# the sha256 of the tiny looped model's lowered programs at the parent
# commit (15daa31; JAX 0.9.0): the recurrent kind left what a
# model without it lowers to as it was (tests/test_ouro.py holds the
# other three models to the same)
PARENT_SHA256 = {
    "tick": "eec70a227efa4c91b093efac1a4f550945ef301f9c02e474a63741c896632181",
    "chunk":
        "e4f50d350b9ecb39850d4867e7e8b06e301cc2ff8b190fd4fa621f20f6b5d5b3",
    "prefill":
        "ce1ac71056c5b9bbfc3e2b40b91d46eeeb580551463b7dfd8f2be14d2a2a979e",
}


@pytest.mark.parametrize("program", sorted(PARENT_SHA256))
def test_the_looped_models_programs_are_the_parents_text(program):
    from hyperion_tpu.models.ouro import Ouro, ouro_tiny_config

    model = Ouro(ouro_tiny_config())
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init_params(jax.random.key(0))))
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=8))
    text = lowered(eng, program)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_SHA256[program]


def test_scopes_of_the_programs_name_the_recurrent_parts(tiny, engine):
    """`recurrent/{conv, gates, step, out}` in the tick, `scan` in a
    prompt's window, the GQA layer under `full`, as `obs/xprof.py`
    groups device time."""
    from hyperion_tpu.obs.xprof import scope_of
    import re

    def scopes(text):
        return {re.sub(r"layer_\d+", "layer_*", scope_of(n)[1])
                for n in re.findall(r'op_name="([^"]+)"', text)}

    eng = engine
    tick = scopes(eng._tick_jit.lower(
        eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
        eng._cache, eng._state, eng._rows_on_device(),
        jnp.asarray(eng._live_mask())).compile().as_text())
    for part in ("qkv_proj", "conv", "gates", "step", "out"):
        assert any(f"layer_*/recurrent/{part}" in s for s in tick), part
    assert not any("recurrent/scan" in s for s in tick)
    assert any("layer_*/full/attn/kv_read" in s for s in tick)
    for part in ("shared", "combine"):
        assert any(f"layer_*/moe/{part}" in s for s in tick), part
    assert any("lm_head" in s for s in tick)
    chunk = scopes(eng._chunk_jit.lower(
        eng.model, eng.variables, eng._cache,
        jnp.zeros((1, 8), jnp.int32), eng._rows_on_device(0, rows={
            k: np.zeros((eng._mb,), np.int32) for k in eng._kinds}),
        jnp.int32(0)).compile().as_text())
    assert any("layer_*/recurrent/scan" in s for s in chunk)


# ------------------------------------------------ the tick's state kernel


@contextlib.contextmanager
def state_kernel_forced():
    """`select_state_update_impl` answering as on a TPU at widths in
    whole lane tiles: the tick's form takes the kernel (through the
    interpreter here), every other form keeps its own. No option of the
    model or the engine is involved: there is none."""
    select = solar_open2.select_state_update_impl

    def forced(window, slot_rows, k, v, backend):
        return select(window, slot_rows, 128, 128, "tpu")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(solar_open2, "select_state_update_impl", forced)
        yield


def twin_of(cfg, name):
    """The model under another identity: the engine's jits are shared
    process-wide and keyed by the model, so the twin holds traces of
    its own."""
    return type(f"SolarOpen2_{name}", (SolarOpen2,), {})(cfg)


def test_the_selector_keeps_every_other_form_dense():
    """Only the tick's form (one position, the cache's rows in order) on
    a TPU at widths in whole lane tiles takes the kernel."""
    select = solar_open2.select_state_update_impl
    assert select(1, True, 128, 128, "tpu") == "kernel"
    for window, slot_rows, k, v, backend in [
            (512, False, 128, 128, "tpu"),  # a chunk, a prefill bucket
            (64, False, 128, 128, "tpu"),
            (1, False, 128, 128, "tpu"),    # a one-token prefill (`rows`)
            (8, False, 128, 128, "tpu"),    # the cache-less forward
            (1, True, 8, 8, "tpu"),         # the tiny configuration
            (1, True, 128, 64, "tpu"),
            (1, True, 128, 128, "cpu")]:    # the CPU backend
        assert select(window, slot_rows, k, v, backend) == "dense"


def test_each_form_asks_the_selector_with_its_own_shape(tiny):
    """What the KDA layer asks the selector, by form: only the tick's
    call says its rows are the cache's own."""
    cfg, model, params = tiny
    asked = []
    select = solar_open2.select_state_update_impl

    def spy(window, slot_rows, k, v, backend):
        asked.append((window, slot_rows, k, v, backend))
        return select(window, slot_rows, k, v, backend)

    cache = init_paged_cache(cfg, 3 * 16 + 1, 4, slots=3)
    table = jnp.zeros((1, 16), jnp.int32)
    forms = {
        "forward": lambda: model.apply({"params": params},
                                       jnp.ones((1, 8), jnp.int32)),
        "prefill": lambda: model.apply(
            {"params": params}, jnp.ones((1, 8), jnp.int32), cache=cache,
            cache_index=jnp.int32(0), block_tables={
                FULL: table, RECURRENT: jnp.asarray([1])},
            state_len=jnp.asarray([5])),
        "one_token_prefill": lambda: model.apply(
            {"params": params}, jnp.ones((1, 1), jnp.int32), cache=cache,
            cache_index=jnp.int32(0), block_tables={
                FULL: table, RECURRENT: jnp.asarray([1])},
            state_len=jnp.asarray([1])),
        "tick": lambda: model.apply(
            {"params": params}, jnp.ones((3, 1), jnp.int32), cache=cache,
            cache_index=jnp.zeros((3,), jnp.int32),
            block_tables={FULL: jnp.zeros((3, 16), jnp.int32)},
            state_len=jnp.asarray([1, 0, 1])),
    }
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(solar_open2, "select_state_update_impl", spy)
        for name, fn in forms.items():
            asked.clear()
            jax.eval_shape(fn)
            # once a KDA layer, each with the same question
            assert len(asked) == 3 and len(set(asked)) == 1, name
            window, slot_rows, k, v, backend = asked[0]
            assert (k, v, backend) == (8, 8, "cpu")
            assert slot_rows == (name == "tick"), name
            assert select(window, slot_rows, 128, 128, "tpu") == (
                "kernel" if name == "tick" else "dense"), name


def test_the_ticks_kernel_gives_the_dense_forms_logits(tiny):
    """The tick's form through the kernel against the dense form, on
    one cache with a state in every row: the logits of the rows that
    advance agree to 1e-5; the row that does not (a lane mid-chunk)
    keeps its S and its conv tail bit for bit on both paths, and the
    advanced rows' state agrees."""
    cfg, model, params = tiny
    MB = 16
    cache = init_paged_cache(cfg, 3 * MB + 1, 4, slots=3)
    keys = iter(jax.random.split(jax.random.key(8), 8))
    for layer in cache[1:]:
        for name in ("S", "conv"):
            layer[name] = 0.5 * jax.random.normal(
                next(keys), layer[name].shape, layer[name].dtype)
    tables = jnp.asarray(np.arange(1, 3 * MB + 1).reshape(3, MB), jnp.int32)
    args = (jnp.asarray([[5], [7], [9]], jnp.int32),)
    kw = dict(cache_index=jnp.asarray([6, 3, 11], jnp.int32),
              block_tables={FULL: tables}, state_len=jnp.asarray([1, 0, 1]))

    def tick():
        fn = jax.jit(lambda v, ids, c: model.apply(v, ids, cache=c, **kw))
        return fn({"params": params}, *args, cache), jax.make_jaxpr(fn)(
            {"params": params}, *args, cache)

    (dense, dense_cache), plain = tick()
    with state_kernel_forced():
        (got, got_cache), kernel = tick()
    assert "pallas_call" in str(kernel) and "pallas_call" not in str(plain)
    scale = max(1.0, float(dense.std()))
    for row in (0, 2):
        assert float(jnp.abs(got[row] - dense[row]).max()) < 1e-5 * scale
    for before, a, b in zip(cache[1:], dense_cache[1:], got_cache[1:]):
        for name in ("S", "conv"):
            np.testing.assert_array_equal(np.asarray(b[name][1]),
                                          np.asarray(before[name][1]))
            np.testing.assert_array_equal(np.asarray(a[name][1]),
                                          np.asarray(before[name][1]))
        for name in ("S", "conv"):
            np.testing.assert_allclose(np.asarray(b[name][::2]),
                                       np.asarray(a[name][::2]), atol=1e-6)


def chunking(eng, cfg):
    """Drive `eng` until one slot decodes while a long prompt prefills
    in chunks; the chunking slot."""
    short, long = requests(cfg, [(4, 30), (40, 3)], seed=4)
    eng.submit(short)
    drain(eng, until=lambda: len(short.tokens) >= 2)
    eng.submit(long)
    drain(eng, until=lambda: bool(eng._chunking))
    (slot,) = eng._chunking
    return slot


def states(eng, slot):
    return [{k: np.asarray(layer[k][slot]) for k in ("S", "conv")}
            for layer in eng._cache[1:]]


@pytest.mark.parametrize("fault", [None, "ticks_advance_a_chunking_lane"])
def test_on_the_kernel_a_chunking_lane_keeps_its_state(tiny, fault):
    """The engine's tick with the state update on the kernel: a slot
    that prefills in chunks leaves the tick as it entered it, bit for
    bit, and the record counts the rows the kernel visited (the live
    one, three layers) of the nine the state holds. With the check's
    fault planted (the tick advances every lane) the kernel visits the
    chunking lane too and its state moves: the fault is one of the path
    that runs."""
    from bench_harness import solar_open2_faults as faults

    cfg, _, params = tiny
    planted = faults.system(fault) if fault else contextlib.nullcontext()
    with state_kernel_forced(), planted:
        eng = Engine(twin_of(cfg, f"kernel_{fault}"), {"params": params},
                     EngineConfig(**ENGINE))
        assert eng._state_form == "kernel"
        slot = chunking(eng, cfg)
        before = states(eng, slot)
        eng._tick_device()
        after = states(eng, slot)
        if fault is None:
            for was, now in zip(before, after):
                for k in ("S", "conv"):
                    np.testing.assert_array_equal(now[k], was[k])
            assert eng._count_state() == {"state_rows_visited": 3,
                                          "state_rows_held": 9}
        else:
            assert any(not np.array_equal(now["S"], was["S"])
                       for was, now in zip(before, after))
            assert eng._count_state() == {"state_rows_visited": 9,
                                          "state_rows_held": 9}


def test_the_tick_record_counts_the_state_rows_visited(tiny, engine):
    """Every decode tick's record says how many state rows (rows x
    recurrent layers) its update read and wrote: off a TPU the dense
    form, every slot's rows; a step without a tick says nothing."""
    cfg, _, _ = tiny
    eng = engine
    assert eng._state_form == "dense"
    for r in requests(cfg, [(5, 4), (30, 3)], seed=6):
        eng.submit(r)
    drain(eng)
    recs = eng.tickprof.tail(256)
    ticks = [r["c"] for r in recs if "recurrent_rows" in r["c"]]
    assert ticks and all(c["state_rows_visited"] == 9
                         and c["state_rows_held"] == 9 for c in ticks)
    assert all("state_rows_visited" not in r["c"] for r in recs
               if "recurrent_rows" not in r["c"])
