"""`paged_kv_write` (models/llama.py): a prompt-length window goes into
the KV pool a block at a time, everything else row by row, and whichever
grain a call takes, every pool block but the null block 0 holds what the
row scatter leaves there, bit for bit. The tick programs of the three
tiny models lower to the text they had when the rows were the only
grain."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperion_tpu.models import afmoe, llama, smallthinker
from hyperion_tpu.serve.engine import Engine, EngineConfig

BS, HKV, D, NB = 16, 2, 8, 160


def _row_scatter(pool, new, tables, base):
    """The contract in numpy, a position at a time: logical position
    `base[b] + t` of row b lands at block `tables[b, p // bs]`, offset
    `p % bs`; what the table does not cover lands in block 0."""
    pool = np.array(pool)
    B, T = new.shape[:2]
    bs, MB = pool.shape[2], tables.shape[1]
    for b in range(B):
        for t in range(T):
            p = int(base[b]) + t
            phys = tables[b, p // bs] if p < MB * bs else 0
            pool[phys, :, p % bs, :] = new[b, t]
    return pool


def _case(B, T, base, MB, holes, seed=0):
    rng = np.random.default_rng(seed)
    pools = {kv: jnp.asarray(rng.standard_normal((NB, HKV, BS, D)),
                             jnp.bfloat16) for kv in ("k", "v")}
    new = {kv: jnp.asarray(rng.standard_normal((B, T, HKV, D)),
                           jnp.bfloat16) for kv in ("k", "v")}
    tables = rng.permutation(np.arange(1, NB))[:B * MB].reshape(
        B, MB).astype(np.int32)
    tables[:, :holes] = 0      # blocks a windowed kind has let go
    return pools, new, tables, np.asarray(base, np.int32)


# (B, T, base, table columns, leading null entries)
CASES = {
    # one block, four blocks, a chunk of 512: aligned, the block path
    "T=bs aligned": (1, BS, [0], 8, 0),
    "T=4bs aligned": (1, 4 * BS, [2 * BS], 8, 0),
    "T=512 aligned": (1, 512, [0], 40, 0),
    "T=512 aligned at a chunk's multiple": (1, 512, [512], 72, 0),
    # a prefix hit that ended mid-block: the rows write it
    "T=bs unaligned": (1, BS, [5], 8, 0),
    "T=4bs unaligned": (1, 4 * BS, [BS + 7], 8, 0),
    "T=512 unaligned": (1, 512, [3], 40, 0),
    # several rows, each at its own base
    "B=3 aligned": (3, 4 * BS, [0, BS, 3 * BS], 8, 0),
    "B=3 one row unaligned": (3, 4 * BS, [0, BS + 1, 3 * BS], 8, 0),
    "B=3 T=512 aligned": (3, 512, [0, 512, BS], 48, 0),
    # bucket padding past the table (`cols >= MB * bs`)
    "table shorter than the window, aligned": (1, 4 * BS, [BS], 3, 0),
    "table shorter than the window, unaligned": (1, 4 * BS, [BS + 3], 3, 0),
    "B=3 tables shorter than the window": (3, 4 * BS, [0, BS, 2 * BS], 3, 0),
    # a windowed kind's table: the entries behind the window are null
    "leading entries 0, aligned": (1, 4 * BS, [BS], 8, 3),
    "leading entries 0, unaligned": (1, 4 * BS, [BS + 9], 8, 3),
    "B=3 leading entries 0": (3, 4 * BS, [0, BS, 2 * BS], 8, 2),
    # an inactive lane: a table of nulls
    "a table of nulls": (1, 4 * BS, [0], 8, 8),
    # windows that fill no block: the row path, as ever
    "T=1 (a tick)": (3, 1, [0, 17, 46], 8, 0),
    "T=5 (a verify window)": (3, 5, [14, 17, 46], 8, 0),
    "T=8 (the smallest bucket)": (1, 8, [12], 8, 0),
}


@pytest.mark.parametrize("name", list(CASES))
def test_pools_equal_the_row_scatters_outside_block_0(name):
    B, T, base, MB, holes = CASES[name]
    pools, new, tables, base = _case(B, T, base, MB, holes)
    args = (new["k"], new["v"], jnp.asarray(tables), jnp.asarray(base))
    got = jax.jit(llama.paged_kv_write)(pools, *args)
    rows = jax.jit(llama._kv_write_rows)(pools["k"], pools["v"], *args)
    for kv, out, row in zip(("k", "v"), got, rows):
        want = _row_scatter(pools[kv], np.asarray(new[kv]), tables, base)
        out, row = np.asarray(out), np.asarray(row)
        assert out.dtype == want.dtype and out.shape == want.shape
        # bit for bit: against the contract and against the row grain
        assert (out[1:].view(np.uint16) == want[1:].view(np.uint16)).all()
        assert (out[1:].view(np.uint16) == row[1:].view(np.uint16)).all()
    # which grain ran: the block path skips null entries altogether, so
    # block 0 keeps what it held; the rows send them there
    by_block = T % BS == 0 and all(b % BS == 0 for b in base)
    nulls = holes > 0 or any(
        b + T > MB * BS for b in base)
    if by_block:
        assert (np.asarray(got[0][0]) == np.asarray(pools["k"][0])).all()
    elif nulls:
        assert (np.asarray(got[0][0]) != np.asarray(pools["k"][0])).any()


@pytest.mark.parametrize("T, conditional", [
    (1, False), (5, False), (8, False), (BS + 8, False),
    (BS, True), (4 * BS, True), (512, True)])
def test_the_grain_is_chosen_from_the_windows_shape(T, conditional):
    """Static part of the choice: only a window of whole blocks holds
    the conditional (and the block scatter in one branch of it); every
    other window's program is the row scatter alone."""
    pools, new, tables, base = _case(1, T, [0], 40, 0)
    text = str(jax.make_jaxpr(llama.paged_kv_write)(
        pools, new["k"], new["v"], jnp.asarray(tables), jnp.asarray(base)))
    assert ("cond[" in text) == conditional
    # two pools, so two scatters a grain
    assert text.count("scatter[") == (4 if conditional else 2)


@pytest.mark.parametrize("T, base, by_block", [
    (64, [0, 16], True), (64, [0, 17], False), (8, [0], False),
    (1, [16], False), (512, [1024], True)])
def test_the_host_asks_the_programs_question(T, base, by_block):
    """`kv_write_by_block` answers for the engine's counters (numpy)
    what it answers inside the program (traced)."""
    host = llama.kv_write_by_block(T, BS, np.asarray(base, np.int32))
    traced = jax.jit(
        lambda b: jnp.asarray(llama.kv_write_by_block(T, BS, b)))(
            jnp.asarray(base, jnp.int32))
    assert bool(host) == bool(traced) == by_block


# --- lowering guards: the ticks' programs are the parent's text -------


def _parent_paged_kv_write(cache, k, v, block_tables, base):
    """`paged_kv_write` as it stood before PR 32 gave it a second
    grain: what the tick programs were lowered from."""
    T = k.shape[1]
    Hkv, bs = cache["k"].shape[1], cache["k"].shape[2]
    MB = block_tables.shape[1]
    L = MB * bs
    with jax.named_scope("kv_write"):
        cols = base[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        phys = jnp.where(
            cols < L,
            jnp.take_along_axis(
                block_tables, jnp.clip(cols // bs, 0, MB - 1), axis=1),
            jnp.int32(0),
        )
        off = cols % bs
        rows = ((phys[:, :, None] * Hkv
                 + jnp.arange(Hkv, dtype=jnp.int32)) * bs
                + off[:, :, None])

        def write(pool, new):
            flat = pool.reshape(-1, pool.shape[-1])
            return flat.at[rows].set(new.astype(pool.dtype)).reshape(
                pool.shape)

        return write(cache["k"], k), write(cache["v"], v)


def _tiny(family):
    if family == "llama":
        return llama.Llama, llama.llama_tiny_config(max_len=64), dict(
            slots=3, max_len=48, eos_id=None)
    if family == "afmoe":
        return afmoe.Afmoe, afmoe.afmoe_tiny_config(experts_held=(2, 4)), \
            dict(slots=3, max_len=64, block_size=4, prefill_chunk=8,
                 prefix_cache=False)
    return smallthinker.Smallthinker, smallthinker.smallthinker_tiny_config(), \
        dict(slots=3, max_len=64, block_size=4, prefill_chunk=8,
             prefix_cache=False)


def _lowered(cls, cfg, engine, program):
    class Twin(cls):
        """The same model under another identity: the engine's jits are
        shared process-wide and keyed by the model, so each twin is
        traced afresh."""

    model = Twin(cfg)
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init_params(jax.random.key(0))))
    eng = Engine(model, {"params": params}, EngineConfig(**engine))
    if program == "tick":
        return eng._tick_jit.lower(
            eng.model, eng.cfg.eos_id, eng.cfg.pad_id, eng.variables,
            eng._cache, eng._state, eng._rows_on_device(),
            jnp.asarray(eng._live_mask())).as_text()
    bt_row = {k: jnp.zeros((eng._mb,), jnp.int32) for k in eng._kinds}
    return eng._prefill_jit.lower(
        eng.model, eng.cfg.eos_id, eng.variables, eng._cache, eng._state,
        jnp.zeros((1, 8), jnp.int32), bt_row, jnp.int32(0), jnp.int32(0),
        jnp.int32(5), jnp.float32(0), jnp.int32(0), jnp.float32(1),
        jnp.int32(4), jax.random.key(0)).as_text()


@pytest.mark.parametrize("family, program", [
    ("llama", "tick"), ("afmoe", "tick"), ("smallthinker", "tick"),
    # the smallest bucket under a block of 16: a prefill on the row path
    ("llama", "prefill"),
    # and one that fills whole blocks (8 positions, blocks of 4): there
    # the text changes, and holds the conditional
    ("afmoe", "prefill"),
])
def test_row_path_programs_lower_to_the_parents_text(family, program):
    cls, cfg, engine = _tiny(family)
    now = _lowered(cls, cfg, engine, program)
    with pytest.MonkeyPatch.context() as mp:
        for module in (llama, afmoe, smallthinker):
            mp.setattr(module, "paged_kv_write", _parent_paged_kv_write)
        parent = _lowered(cls, cfg, engine, program)
    assert "stablehlo.scatter" in now
    if (family, program) == ("afmoe", "prefill"):
        # a conditional a layer more than the parent's (whose one is
        # the sampler's)
        cases = [len(re.findall(r"stablehlo\.case", t))
                 for t in (now, parent)]
        assert cases[0] == cases[1] + cfg.n_layers
    else:
        assert now == parent
