"""`models/ouro.py` (ByteDance Ouro: the same layers applied
`total_ut_steps` times a token over shared weights, a norm inside the
loop, an exit gate) and the serving cache of `steps x layers` layers
behind one block table, against the plain reference
`benchmarks/reference/ouro.py`: float32, tiny sizes (four layers in two
scans, four steps, contexts to 60)."""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_harness.ouro_faults import FAULTS, fault as wrong_reference
from benchmarks.reference import ouro as reference
from hyperion_tpu.models.llama import (
    init_paged_cache,
    paged_cache_block_bytes,
    paged_kv_write,
    segment_shift,
)
from hyperion_tpu.models.ouro import Ouro, OuroConfig, ouro_tiny_config
from hyperion_tpu.serve.engine import Engine, EngineConfig
from hyperion_tpu.serve.queue import Request

TOL = 1e-4


def ref_kw(cfg, **over):
    return {**dict(n_layers=cfg.n_layers, head_dim=cfg.head_dim,
                   steps=cfg.total_ut_steps, theta=cfg.rope_theta,
                   eps=cfg.norm_eps), **over}


def make(cfg, seed=1):
    """Weights with every norm scale and the gate's bias moved off
    their initial values and every matrix ten times its initial size,
    so that each of them matters."""
    model = Ouro(cfg)
    params = model.init_params(jax.random.key(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    leaves = [x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
              if path[-1].key in ("weight", "bias") else 10 * x
              for (path, x), k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


@pytest.fixture(scope="module")
def tiny():
    cfg = ouro_tiny_config()
    return (cfg, *make(cfg))


def ids_of(cfg, n, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, n), 1,
                              cfg.vocab_size)


# ------------------------------------------------------------- the model


def test_config_is_the_published_one():
    c = OuroConfig()
    assert (c.d_model, c.n_layers, c.n_heads, c.n_kv_heads, c.head_dim,
            c.ff_dim, c.vocab_size, c.total_ut_steps,
            c.early_exit_threshold, c.rope_theta, c.norm_eps) == \
        (2048, 48, 16, 16, 128, 5632, 49152, 4, 1.0, 1e6, 1e-6)
    assert c.layer_kinds == (("full", 0),) * 48
    # one scan over all the layers unless told otherwise
    assert (c.cache_steps, c.pool_layers, c.cache_segments) == \
        (4, 48, 192)
    c = dataclasses.replace(c, pool_layers=16)
    assert (c.pool_layers, c.cache_segments) == (16, 64)
    with pytest.raises(ValueError, match="does not divide"):
        dataclasses.replace(c, pool_layers=5)


def test_the_weights_are_stacked_a_scan_and_shared_by_the_steps(tiny):
    """The same leaves serve every step: the tree has one copy of the
    layers, `pool_layers` of them a leaf."""
    cfg, _, params = tiny
    shapes = jax.tree.map(lambda x: x.shape, params)
    assert set(shapes) == {"embed_tokens", "loop", "lm_head"}
    assert set(shapes["loop"]) == {"layers_0", "layers_1", "step_norm",
                                   "exit_gate"}
    lay = shapes["loop"]["layers_0"]
    assert lay["q_proj"]["kernel"] == (2, 32, 32)       # [n, out, in]
    assert lay["gate_proj"]["kernel"] == (2, 32, 48)    # [n, in, out]
    assert lay["down_proj"]["kernel"] == (2, 48, 32)
    assert {k for k, v in lay.items() if "weight" in v} == {
        "input_norm", "attn_post_norm", "pre_mlp_norm", "mlp_post_norm"}
    assert shapes["loop"]["exit_gate"] == {"kernel": (32, 1), "bias": (1,)}
    two = dataclasses.replace(cfg, total_ut_steps=2)
    assert jax.tree.map(lambda x: x.shape, jax.eval_shape(
        lambda: Ouro(two).init_params(jax.random.key(0)))) == shapes


@pytest.mark.parametrize("batch,length", [(1, 5), (2, 23), (1, 60)])
def test_model_agrees_with_the_plain_reference(tiny, batch, length):
    """Logits and the exit distribution, to 1e-4 in float32."""
    cfg, model, params = tiny
    ids = ids_of(cfg, length, batch=batch)
    got, p = model.apply({"params": params}, ids)
    want, want_p, _ = reference.forward(params, ids, **ref_kw(cfg))
    assert got.shape == (batch, length, cfg.vocab_size)
    assert p.shape == (batch, length, cfg.total_ut_steps)
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert float(jnp.abs(p - want_p).max()) < TOL
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-5)
    # a gate that matters: no step has all of the mass everywhere
    assert float(p.max(axis=(0, 1)).min()) > 1e-3


@pytest.mark.parametrize("steps", [1, 2, 3])
def test_fewer_steps_are_another_model(tiny, steps):
    cfg, _, params = tiny
    short = dataclasses.replace(cfg, total_ut_steps=steps)
    ids = ids_of(cfg, 12)
    got, p = Ouro(short).apply({"params": params}, ids)
    want, want_p, _ = reference.forward(
        params, ids, **ref_kw(cfg, steps=steps))
    assert p.shape[-1] == steps
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert float(jnp.abs(p - want_p).max()) < TOL
    full = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - full).max()) > 0.05 * float(full.std())


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_each_fault_fails_the_comparison(tiny, name):
    """What `tests/bench_harness/ouro_faults.py` puts wrong in the
    reference is far outside the tolerance the model meets, and gone
    when the block ends."""
    cfg, model, params = tiny
    ids = ids_of(cfg, 24)
    got, _ = model.apply({"params": params}, ids)
    with wrong_reference(name):
        wrong = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - wrong).max()) > 100 * TOL * float(got.std())
    right = reference.logits(params, ids, **ref_kw(cfg))
    assert float(jnp.abs(got - right).max()) < TOL * float(right.std())


def test_a_threshold_under_one_is_refused_where_the_model_is_served(tiny):
    """Per-token depth is not run: loudly, at the engine's construction
    and at the model's cached call; the full forward still returns the
    exit distribution such a threshold would be held against."""
    cfg, _, params = tiny
    early = dataclasses.replace(cfg, early_exit_threshold=0.9)
    model = Ouro(early)
    _, p = model.apply({"params": params}, ids_of(cfg, 6))
    assert p.shape == (1, 6, 4)
    with pytest.raises(ValueError, match="per-token depth is not run"):
        Engine(model, {"params": params},
               EngineConfig(slots=2, max_len=32, block_size=4))
    with pytest.raises(ValueError, match="early_exit_threshold 0.9"):
        model.apply({"params": params}, ids_of(cfg, 6),
                    cache=init_paged_cache(cfg, 5, 4), cache_index=0,
                    block_tables=jnp.asarray([[1, 2, 3, 4]], jnp.int32))


# -------------------------------------------------------------- the cache


def test_block_bytes_and_pools_follow_steps_x_layers(tiny):
    cfg, _, _ = tiny
    # 2 (K, V) x 4 layers x 4 steps x 4 positions x 4 heads x 8 x 4 bytes
    assert paged_cache_block_bytes(cfg, 4) == 2 * 16 * 4 * 4 * 8 * 4
    assert paged_cache_block_bytes(cfg, 4, kind="full") == 16384
    cache = init_paged_cache(cfg, 7, 4)
    # a pair of pools a scan: 2 layers x 4 steps segments of 7 blocks
    assert len(cache) == 2
    assert {x.shape for c in cache for x in c.values()} == {(56, 4, 4, 8)}
    assert sum(x.nbytes for c in cache for x in c.values()) == 7 * 16384
    # the published model: 1.5 MiB a position, 24 MiB a block of 16
    assert paged_cache_block_bytes(OuroConfig(), 16) == 24 * 2 ** 20
    assert jax.eval_shape(        # one scan, one pair of pools
        lambda: init_paged_cache(OuroConfig(n_layers=2), 3, 16)
    )[0]["k"].shape == (2 * 4 * 3, 16, 16, 128)
    shapes = jax.eval_shape(
        lambda: init_paged_cache(OuroConfig(pool_layers=16), 385, 16))
    assert len(shapes) == 3
    assert shapes[0]["k"].shape == (16 * 4 * 385, 16, 16, 128)
    assert shapes[0]["k"].size * 2 < 2 ** 31        # bytes: bf16


def test_a_segments_writes_never_land_in_another(tiny):
    """Write a window with every segment in turn, each with values that
    name the segment, then read each pool back: segment s holds s's
    values at the table's blocks, its null block the uncovered
    positions, and nothing else moved."""
    cfg, _, _ = tiny
    NB, bs, segs = 6, 4, cfg.cache_segments
    pool = init_paged_cache(cfg, NB, bs)[0]
    table = jnp.asarray([[3, 5, 0]], jnp.int32)       # 8 positions mapped
    base = jnp.asarray([2], jnp.int32)
    T = 8                                             # 2..9: 8, 9 uncovered
    for s in range(segs):
        new = jnp.full((1, T, cfg.n_kv_heads, cfg.head_dim), s + 1.0)
        k, v = paged_kv_write(pool, new, -new, table, base,
                              segment_shift(pool["k"], jnp.int32(s), segs))
        pool = {"k": k, "v": v}
    k = np.asarray(pool["k"]).reshape(segs, NB, cfg.n_kv_heads, bs, -1)
    v = np.asarray(pool["v"]).reshape(k.shape)
    for s in range(segs):
        assert (k[s, 3, :, 2:] == s + 1).all() and (k[s, 3, :, :2] == 0).all()
        assert (k[s, 5] == s + 1).all()
        assert (k[s, [1, 2, 4]] == 0).all()
        # positions 8 and 9 went to THIS segment's null block
        assert set(np.unique(k[s, 0])) <= {0.0, s + 1.0}
        assert (k[s, 0] == s + 1).any()
    np.testing.assert_array_equal(v, -k)


def paged(cfg, slots, max_len, bs):
    MB = max_len // bs
    tables = jnp.arange(1, slots * MB + 1, dtype=jnp.int32).reshape(slots, MB)
    return init_paged_cache(cfg, slots * MB + 1, bs), tables


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_paged_prefill_then_decode_agrees_with_the_reference(tiny, impl):
    """Prefill a prompt, then decode token by token through the cache
    of `steps x layers` layers, by the gather and by the kernel (the
    interpreter): every step's logits are the reference's full forward,
    to 1e-4."""
    cfg, _, params = tiny
    model = Ouro(dataclasses.replace(cfg, paged_attn_impl=impl))
    ids = ids_of(cfg, 22, seed=5, batch=2)
    want = reference.logits(params, ids, **ref_kw(cfg))
    scale = TOL * float(want.std())
    cache, tables = paged(cfg, 2, 32, 4)
    P = 13
    got, cache = model.apply({"params": params}, ids[:, :P], cache=cache,
                             cache_index=0, block_tables=tables)
    assert float(jnp.abs(got - want[:, :P]).max()) < scale
    for t in range(P, ids.shape[1]):
        got, cache = model.apply(
            {"params": params}, ids[:, t:t + 1], cache=cache,
            cache_index=jnp.full((2,), t, jnp.int32), block_tables=tables)
        assert float(jnp.abs(got[:, 0] - want[:, t]).max()) < scale, t


# ------------------------------------------------------------- the engine


ENGINE = dict(slots=3, max_len=64, block_size=4)


def drain(eng):
    while not eng.idle:
        eng.step()


def requests(cfg, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt_ids=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g, id=f"r{i}")
            for i, (n, g) in enumerate(shapes)]


def assert_greedy(cfg, params, reqs):
    for r in reqs:
        assert r.status == "done" and len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt_ids, np.asarray(r.tokens, np.int32)])
        want = reference.logits(params, jnp.asarray(seq[None]),
                                **ref_kw(cfg))[0]
        P = len(r.prompt_ids)
        assert (np.asarray(want[P - 1:P - 1 + len(r.tokens)].argmax(-1))
                == np.asarray(r.tokens)).all(), r.id


@pytest.mark.parametrize("admission", ["reserve", "optimistic"])
def test_engine_serves_short_and_long_requests_in_one_queue(tiny, admission):
    """A looped model through the same jits, block manager, admissions
    and prefix cache as every other: one chain a slot for all four
    steps, served tokens the reference's greedy tokens, nothing
    compiled after warm-up, and the tick record says what the loop
    did."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params},
                 EngineConfig(**ENGINE, admission=admission, num_blocks=30))
    assert list(eng._kinds) == ["full"] and eng.prefix is not None
    assert (eng._steps, eng._segments) == (4, 8)
    assert eng._cache[0]["k"].shape[0] == 8 * 30
    eng.warmup([32])
    compiled = eng.compile_stats()
    reqs = requests(cfg, [(5, 6), (30, 20), (17, 12), (7, 3), (21, 30),
                          (3, 40)])
    for r in reqs:
        eng.submit(r)
    drain(eng)
    assert eng.compile_stats() == compiled
    eng.mgr.check()
    assert eng.mgr.reserved == 0
    assert_greedy(cfg, params, reqs)
    ticks = [r["c"] for r in eng.tickprof.tail(256) if "device" in r["s"]]
    assert ticks and all(
        (c["loop_steps"], c["layer_passes"]) == (4, 16) for c in ticks)
    # a step that ran no tick counts no pass
    idle = [r["c"] for r in eng.tickprof.tail(256) if "device" not in r["s"]]
    assert all("loop_steps" not in c for c in idle)
    assert eng.tickprof.snapshot(1e9)["loop"] == {
        "ticks": len(ticks), "steps": 4, "layer_passes": 16}
    ledger = eng.memory_ledger()
    assert ledger["kv_bytes_per_token"] == 16384 // 4
    assert ledger["kv_pool_bytes"] == 30 * 16384
    assert ledger["kv_by_kind"]["full"]["pool_bytes"] == 30 * 16384


def test_other_models_records_and_ledgers_stay_as_they_are():
    from hyperion_tpu.models.llama import Llama, llama_tiny_config

    cfg = llama_tiny_config()
    model = Llama(cfg)
    params = model.init_params(jax.random.key(0))
    eng = Engine(model, {"params": params}, EngineConfig(**ENGINE))
    assert (eng._steps, eng._segments) == (1, 1)
    eng.submit(Request(prompt_ids=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=3, id="a"))
    drain(eng)
    recs = eng.tickprof.tail(16)
    assert recs and not any(
        k in r["c"] for r in recs for k in ("loop_steps", "layer_passes"))
    assert "loop" not in eng.tickprof.snapshot(1e9)
    # 2 x 2 layers x 4 heads x 16 x 4 bytes a position
    assert eng.memory_ledger()["kv_bytes_per_token"] == 2 * 2 * 4 * 16 * 4
    np.testing.assert_array_equal(
        np.asarray(eng._in_every_segment([3, 7])), [3, 7])


@pytest.mark.parametrize("loop, says", [
    ({"ticks": 5, "steps": 4, "layer_passes": 192},
     "4 passes over 48 layers a tick (192 cache layers)"),
    (None, None),
])
def test_doctor_names_the_loop_when_the_record_has_it(tmp_path, loop, says):
    import json

    from hyperion_tpu.obs import doctor

    tp = {"dominant": "device", "dominant_frac": 0.9, "ticks": 5,
          "window_s": 60.0, "total_s": 1.25,
          "segments": {"device": {"s": 1.0, "frac": 0.9}},
          "counters": {"kv_tokens": 10, "prefill_tokens": 0},
          **({"loop": loop} if loop else {})}
    (tmp_path / "telemetry.jsonl").write_text(json.dumps(
        {"kind": "snapshot", "run": "r", "t": 1.0, "metrics": {},
         "tickprof": tp}) + "\n")
    row = next(ln for ln in doctor.render_markdown(
        doctor.diagnose(tmp_path)).splitlines()
        if ln.startswith("| host tick profile"))
    assert (says in row) if says else ("passes over" not in row)


def block_of(eng, block):
    """[pools, segments, 2, H, bs, D]: a block id's keys and values in
    every segment of every pool."""
    ids = np.asarray(eng._in_every_segment([block]))
    return np.stack([np.stack([np.asarray(c["k"])[ids],
                               np.asarray(c["v"])[ids]], 1)
                     for c in eng._cache])


def test_a_prefix_hit_shares_and_a_fork_copies_every_segment(tiny):
    """A later request that starts like an earlier one shares its
    blocks in all `steps x layers` caches (they are one id), and where
    it parts mid-block the copy-on-write copies every segment: the
    copy equals its source everywhere up to the parting, and both
    streams stay the reference's."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params},
                 EngineConfig(**ENGINE, num_blocks=40))
    eng.warmup([32])
    compiled = eng.compile_stats()
    rng = np.random.default_rng(7)
    A = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    B = np.concatenate([A[:18],
                        rng.integers(1, cfg.vocab_size, 6).astype(np.int32)])
    ra = Request(prompt_ids=A, max_new_tokens=4, id="A")
    eng.submit(ra)
    drain(eng)
    src = eng.prefix.lookup(B, len(B) - 1)
    assert len(src.blocks) == 4 and src.cow_src is not None     # 16 + 2
    before = block_of(eng, src.cow_src)
    assert np.abs(before).sum() > 0
    rb = Request(prompt_ids=B, max_new_tokens=4, id="B")
    eng.submit(rb)
    eng.step()                      # admits B: the fork, then its prefill
    seq = eng._seqs[[s for s, r in enumerate(eng._slots) if r is rb][0]]
    assert seq.blocks[:4] == src.blocks and seq.n_shared == 4
    fork = block_of(eng, seq.blocks[4])
    assert fork.shape[:3] == (2, 8, 2)
    # the two agreeing positions, in every pool and segment; the source
    # untouched
    np.testing.assert_array_equal(fork[..., :2, :], before[..., :2, :])
    assert (np.abs(fork[..., :2, :]).sum(axis=(-1, -2, -3)) > 0).all()
    np.testing.assert_array_equal(block_of(eng, src.cow_src), before)
    drain(eng)
    s = eng.metrics.summary()
    assert s["cow_copies"] == 1 and s["prefix_hit_rate"] > 0
    assert eng.compile_stats() == compiled
    assert_greedy(cfg, params, [ra, rb])


def test_the_host_tier_spills_and_restores_every_segment(tiny, tmp_path):
    """Radix eviction demotes a block with its `steps x layers` caches
    (one payload entry a cache layer), a re-hit restores them all, and
    the restored stream is the reference's."""
    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=48, block_size=8, num_blocks=8,
        admission="optimistic", queue_capacity=16, host_cache_mb=8))
    eng.warmup()
    compiled = eng.compile_stats()
    rng = np.random.default_rng(83)
    shared = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)

    def tail(n):
        return np.concatenate(
            [shared, rng.integers(1, cfg.vocab_size, n).astype(np.int32)])

    seed_req = Request(prompt_ids=tail(3), max_new_tokens=4, id="seed")
    eng.submit(seed_req)
    drain(eng)
    growers = [Request(prompt_ids=rng.integers(1, cfg.vocab_size, 6)
                       .astype(np.int32), max_new_tokens=12, id=f"g{i}")
               for i in range(3)]
    for r in growers:
        eng.submit(r)
        eng.step()
    drain(eng)
    s = eng.metrics.summary()
    assert s["host_spilled_blocks"] >= 2, s
    payload = next(iter(eng.host._chains.values()))
    # 2 pools x 8 segments cache layers, K and V, [H, bs, D]
    assert payload.shape == (16, 2, 4, 8, 8)
    rehit = Request(prompt_ids=tail(4), max_new_tokens=4, id="rehit")
    eng.submit(rehit)
    drain(eng)
    s = eng.metrics.summary()
    assert s["tier_hits_host"] >= 1 and s["host_restored_blocks"] >= 2, s
    assert eng.compile_stats() == compiled
    assert_greedy(cfg, params, [seed_req, rehit] + growers)


# ----------------------------------------------------------- the programs


def lowered(eng, which: str, compiled: bool = False) -> str:
    """The text of one of the engine's three programs at its own shapes
    (no locations: `as_text()` leaves them out)."""
    bt_row = {k: jnp.zeros((eng._mb,), jnp.int32) for k in eng._kinds}
    if which == "tick":
        low = eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, eng._rows_on_device(),
            jnp.asarray(eng._live_mask()))
    elif which == "chunk":
        low = eng._chunk_jit.lower(
            eng.model, eng.variables, eng._cache,
            jnp.zeros((1, eng.cfg.prefill_chunk), jnp.int32), bt_row,
            jnp.int32(0))
    else:
        low = eng._prefill_jit.lower(
            eng.model, eng.cfg.eos_id, eng.variables, eng._cache, eng._state,
            jnp.zeros((1, 8), jnp.int32), bt_row, jnp.int32(0), jnp.int32(0),
            jnp.int32(5), jnp.float32(0), jnp.int32(0), jnp.float32(1),
            jnp.int32(4), jax.random.key(0))
    return low.compile().as_text() if compiled else low.as_text()


def zero_engine(model, **over):
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init_params(jax.random.key(0))))
    return Engine(model, {"params": params},
                  EngineConfig(**ENGINE, prefill_chunk=8, **over))


@pytest.mark.parametrize("program", ["tick", "chunk", "prefill"])
@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_a_program_does_not_grow_with_the_loop(impl, program):
    """The steps are a loop in the program: the lowered text holds the
    same number of matrix products, paged-attention calls, scatters and
    loops at `total_ut_steps` 2 and 4, and at four layers a scan as at
    two."""
    def counts(**over):
        model = Ouro(ouro_tiny_config(paged_attn_impl=impl, **over))
        text = lowered(zero_engine(model), program)
        return {op: len(re.findall(op, text)) for op in (
            r"stablehlo\.dot_general", r"call @_paged_attention",
            r"stablehlo\.scatter", r"stablehlo\.while",
            r"stablehlo\.gather")}

    four = counts()
    assert four[r"stablehlo\.dot_general"] > 0
    assert four[r"stablehlo\.while"] >= 3       # the steps, two scans
    if impl == "pallas" and program == "tick":
        assert four[r"call @_paged_attention"] == 2     # one a scan
    assert counts(total_ut_steps=2) == four
    # twice the layers in as many scans: the same program again
    assert counts(n_layers=8, pool_layers=4) == four


def test_scopes_of_the_tick(tiny):
    """The scopes as `obs/xprof.py` reads them (`scope_of` drops what
    the loops put into an operation's name: `while/body/closed_call`):
    one `layer` for all the layers and steps, summed by the reduction."""
    from hyperion_tpu.obs.xprof import scope_of

    cfg, model, params = tiny
    eng = Engine(model, {"params": params}, EngineConfig(**ENGINE))
    names = set(re.findall(r'op_name="([^"]+)"',
                           lowered(eng, "tick", compiled=True)))
    assert any("Ouro/while/body" in n for n in names)
    scopes = {scope_of(n)[1] for n in names}
    for stage in ("qkv_proj", "rope", "kv_write", "kv_read", "attention",
                  "o_proj", "post_norm"):
        assert f"Ouro/loop/layer/attn/{stage}" in scopes, stage
    for stage in ("gate_up", "down", "post_norm"):
        assert f"Ouro/loop/layer/mlp/{stage}" in scopes, stage
    for scope in ("Ouro/embed_tokens", "Ouro/rope_table",
                  "Ouro/loop/step_norm", "Ouro/lm_head"):
        assert scope in scopes, scope
    # the gate decides nothing at threshold 1 and the compiler drops it
    # from the served programs: the full forward has it
    full = jax.jit(lambda p, i: model.apply({"params": p}, i)).lower(
        params, ids_of(cfg, 8)).compile().as_text()
    assert "Ouro/loop/exit_gate" in {
        scope_of(n)[1] for n in re.findall(r'op_name="([^"]+)"', full)}


# the sha256 of the tiny models' lowered programs at the parent commit
# (bb1ede0, PR 32; JAX 0.9.0): this PR gave `paged_kv_write`,
# `paged_read`, `init_paged_cache` and `RMSNorm` a looped model's
# arguments, and what every other model lowers to is the text it was
PARENT_SHA256 = {
    "llama.tick":
        "a110b8864305c1d084ca028254655528ec72d206ee2bd862611333c16faf8b43",
    "llama.chunk":
        "0385bfe4928bad369182772345949a3e42d896b0ef3685304a4837ed8cd957ae",
    "llama.prefill":
        "ebe946031aa4b661a348a4f9e8afb5bda5b48445dab5a2a9a7c0febe903c8fc1",
    "afmoe.tick":
        "51efe6639b9c6731732868c485e609c7dd342ae867bce9400172f385111c5b24",
    "afmoe.chunk":
        "1743503c3bf1d2262fbef277110dd2c3c9163edbec8a82ffd0d8c2da04f83f92",
    "afmoe.prefill":
        "b366b2460930facc462712749f4cc47b0bcbf27d04a8b8ddaca752e44411e413",
    "smallthinker.tick":
        "aab6e7e4ebf7d8c579801d1942b4bdbcc8307b50b6abb4e1c63a9f31097719ae",
    "smallthinker.chunk":
        "aa05e4e64239138228ba55d2833821c33128f5cf37dad843c6e5ad659555968b",
    "smallthinker.prefill":
        "5f2955c506551bcccba96cdb53f35358a17fe240dd9bb080779ba91e38d9f947",
}


def _served(name):
    if name == "llama":
        from hyperion_tpu.models.llama import Llama, llama_tiny_config

        return Llama(llama_tiny_config()), {}
    if name == "afmoe":
        from hyperion_tpu.models.afmoe import Afmoe, afmoe_tiny_config

        return Afmoe(afmoe_tiny_config(experts_held=(2, 4))), \
            {"prefix_cache": False}
    from hyperion_tpu.models.smallthinker import (
        Smallthinker, smallthinker_tiny_config)

    return Smallthinker(smallthinker_tiny_config()), {"prefix_cache": False}


@pytest.mark.parametrize("case", sorted(PARENT_SHA256))
def test_the_other_models_programs_are_the_parents_text(case):
    name, program = case.split(".")
    model, over = _served(name)
    text = lowered(zero_engine(model, **over), program)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_SHA256[case]
