#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

One process, one TPU chip:  python chip_smoke.py [--seed N]

Drives the two paths users depend on, through the entry points they
call, and checks what comes out by the repo's own means:

  serve    a Llama-2-7B-width model (depth cut to fit 16 GB, printed),
           random bf16 weights from --seed, exported with
           `checkpoint.io.export_gathered` and served by the code
           `python -m hyperion_tpu.cli.main serve` runs, once with
           `--paged-attn gather` and once with `pallas`; then the
           logits of the two read paths and of a plain `Llama.apply`
           are compared on the same tokens.
  train    `cli.main` job language_fsdp for a few optimizer steps at
           `--compile-tier jit` and `jit+pallas`.
  kernels  every Pallas kernel, at the shapes the two phases used,
           compiles to a program that holds a `tpu_custom_call`.

It fails — traceback, non-zero exit, no result line — when JAX finds no
TPU, and when any phase fails. The last line of a passing run is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

`--chips 4` (run by hand on a four-chip host) runs ONLY the collectives
of `runtime.comm_check` and a language_fsdp run on a `1,4,1,1` mesh
against the same seed and global batch on one device of the same host.

`--rehearse` is a switch of this script, not of the program: it shrinks
every size and lifts the TPU requirement so the control flow can be
walked on the CPU backend. It says so on its first line and reports the
true platform in its last, so a rehearsal cannot be read as a chip run.
"""

from __future__ import annotations

import argparse
import csv
import dataclasses
import gc
import io
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

# Every bf16 computation of the model (plain forward, gather read, pallas
# read) is held to an fp32 reference forward of the same weights, as a
# share of the logits' standard deviation: bf16 rounding carried through
# 16 layers at d=4096 lands at a few percent (measured on the chip, see
# PERF.md), a wrong block, mask or layout at 100 %.
NOISE_RMS = 0.10      # RMS error of a path, over the logits' std
PALLAS_VS_GATHER = 1.5   # the kernel's RMS error over the gather path's
# A served token may be a near-tie's other side, never a wrong token:
# its reference logit lies within this many std of the row's maximum
# (a random token lies about 4 std below it).
TOKEN_SLACK = 0.6


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def peak_gb() -> str:
    import jax

    from hyperion_tpu.utils.memory import device_memory_stats

    out = []
    for d in jax.local_devices():
        s = device_memory_stats(d)
        if not s:
            return "memory_stats: not reported on this backend"
        out.append(f"{s['peak_bytes_in_use'] / 2**30:.2f}")
    return "memory_stats peak GiB per device: " + ", ".join(out)


SETTLE_LIMIT = 64 << 20   # main() tightens it for a rehearsal's tiny arrays


def settle(what: str) -> None:
    """Drop a finished phase's arrays and check that they are gone:
    two engines, or an engine and a trainer, do not fit together.
    Counted from the live arrays (so a rehearsal on the CPU sees a leak
    too) and, more loosely, from the allocator where it reports: loaded
    executables stay in device memory (0.11 GiB after one engine)."""
    import jax

    from hyperion_tpu.utils.memory import live_bytes_in_use

    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    used = max(live_bytes_in_use(d) for d in jax.local_devices())
    if live > SETTLE_LIMIT or used > 1 << 30:
        raise RuntimeError(
            f"{what} left {live / 2**30:.2f} GiB of live arrays and "
            f"{used / 2**30:.2f} GiB in use by memory_stats: the next "
            "phase would not fit")


# ------------------------------------------------------------------ serve


@dataclasses.dataclass
class ServeSizes:
    cfg: object            # LlamaConfig
    slots: int
    max_len: int
    chunk: int
    new_tokens: int
    long_prompt: int
    shared_prefix: int
    full_depth: int        # the published depth the cut is measured from


def serve_sizes(rehearse: bool) -> ServeSizes:
    from hyperion_tpu.models.llama import LlamaConfig, llama_tiny_config

    if rehearse:
        cfg = llama_tiny_config(n_kv_heads=2, max_len=256)
        return ServeSizes(cfg, slots=4, max_len=256, chunk=32,
                          new_tokens=8, long_prompt=70, shared_prefix=24,
                          full_depth=cfg.n_layers)
    # Llama-2-7B widths (LlamaConfig defaults: d 4096, 32 heads of 128,
    # ff 11008, vocab 32000, bf16). Depth is the only cut: 0.405 GB per
    # layer + 0.52 GB embeddings/head in bf16, 16 KB of KV per token
    # per layer — 16 layers are 7 GB of weights and 4 GB of pool for
    # 8 slots x 2048 tokens on a 16 GB chip.
    cfg = LlamaConfig(n_layers=16, max_len=2048, remat=False)
    return ServeSizes(cfg, slots=8, max_len=2048, chunk=256,
                      new_tokens=64, long_prompt=1100, shared_prefix=200,
                      full_depth=32)


def make_weights(cfg, seed: int):
    """Random weights in the compute dtype, made on the device leaf by
    leaf: `init_params` would hold the whole tree in fp32 first, which
    at these widths is twice the chip."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from hyperion_tpu.models.llama import Llama

    shapes = jax.eval_shape(
        lambda: Llama(cfg).init_params(jax.random.key(0), seq=8))
    flat = traverse_util.flatten_dict(shapes)
    keys = jax.random.split(jax.random.key(seed), len(flat))
    out = {}
    for key, (path, leaf) in zip(keys, sorted(flat.items())):
        if path[-1] == "weight":          # RMSNorm scales: ones, fp32
            out[path] = jnp.ones(leaf.shape, leaf.dtype)
        else:                             # normal(0.02), as the model inits
            out[path] = (0.02 * jax.random.normal(
                key, leaf.shape, jnp.float32)).astype(cfg.compute_dtype)
    return traverse_util.unflatten_dict(out)


def make_requests(sz: ServeSizes, seed: int) -> tuple[list[dict], list[dict]]:
    """(first wave, second wave). The second wave repeats the first
    wave's shared prefix and is sent only once `shared_a` is done, so
    the radix cache holds the prefix by then."""
    import numpy as np

    rng = np.random.default_rng(seed)
    V = sz.cfg.vocab_size

    def ids(n):
        return [int(t) for t in rng.integers(1, V, n)]

    prefix = ids(sz.shared_prefix)
    n = sz.new_tokens
    first = [
        {"id": "long", "prompt_ids": ids(sz.long_prompt), "max_new_tokens": n},
        {"id": "shared_a", "prompt_ids": prefix + ids(20), "max_new_tokens": n},
        {"id": "short", "prompt_ids": ids(5), "max_new_tokens": n},
        {"id": "mid", "prompt_ids": ids(sz.chunk // 2 + 3), "max_new_tokens": n},
    ]
    second = [
        {"id": "shared_b", "prompt_ids": prefix + ids(33), "max_new_tokens": n},
        {"id": "shared_c", "prompt_ids": prefix + ids(7), "max_new_tokens": n},
    ]
    return first, second


class _TwoWaveStdin:
    """What the server reads as stdin: the first wave at once, the
    second when `gate` opens."""

    def __init__(self, first, second, gate: threading.Event):
        self.first, self.second, self.gate = first, second, gate

    def __iter__(self):
        for r in self.first:
            yield json.dumps(r) + "\n"
        if not self.gate.wait(timeout=900):
            raise RuntimeError("first wave never finished")
        for r in self.second:
            yield json.dumps(r) + "\n"


class _GateStdout(io.StringIO):
    """What the server writes as stdout: keeps the wire lines and opens
    `gate` at the terminal line of request `rid`."""

    def __init__(self, rid: str, gate: threading.Event):
        super().__init__()
        self.rid, self.gate = rid, gate

    def write(self, s):
        n = super().write(s)
        if '"event":"done"' in s and f'"id":"{self.rid}"' in s:
            self.gate.set()
        return n


def run_server(sz: ServeSizes, ckpt: Path, impl: str, out: Path,
               seed: int) -> dict:
    """One life of `hyperion serve` in this process: requests in on
    stdin, token events out on stdout, telemetry to a file."""
    from hyperion_tpu.cli.main import main as cli_main

    out.mkdir(parents=True, exist_ok=True)
    tele = out / "telemetry.jsonl"
    first, second = make_requests(sz, seed)
    gate = threading.Event()
    stdout = _GateStdout("shared_a", gate)
    argv = [
        "serve", "--ckpt", str(ckpt), "--no-tokenizer",
        "--max-len", str(sz.max_len), "--slots", str(sz.slots),
        "--block-size", "16", "--paged-attn", impl, "--prefix-cache",
        "--prefill-chunk", str(sz.chunk),
        "--warmup-lens", str(sz.chunk),
        "--max-new-default", str(sz.new_tokens),
    ]
    log(f"serve[{impl}]: hyperion {' '.join(argv)}")
    old = (sys.stdin, sys.stdout, os.environ.get("HYPERION_TELEMETRY"))
    os.environ["HYPERION_TELEMETRY"] = str(tele)
    sys.stdin, sys.stdout = _TwoWaveStdin(first, second, gate), stdout
    t0 = time.perf_counter()
    try:
        rc = cli_main(argv)
    finally:
        sys.stdin, sys.stdout = old[0], old[1]
        if old[2] is None:
            del os.environ["HYPERION_TELEMETRY"]
        else:
            os.environ["HYPERION_TELEMETRY"] = old[2]
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"serve[{impl}] exited {rc}")

    streams: dict[str, list[int]] = {}
    done: dict[str, int] = {}
    for line in stdout.getvalue().splitlines():
        rec = json.loads(line)
        ev = rec.get("event")
        if ev == "token":
            streams.setdefault(rec["id"], []).append(int(rec["token"]))
        elif ev == "done":
            done[rec["id"]] = int(rec["n_tokens"])
        else:
            raise RuntimeError(f"serve[{impl}] answered {rec}")
    want = {r["id"] for r in first + second}
    if set(done) != want:
        raise RuntimeError(
            f"serve[{impl}]: done for {sorted(done)}, wanted {sorted(want)}")
    for rid in want:
        if done[rid] != sz.new_tokens or len(streams[rid]) != sz.new_tokens:
            raise RuntimeError(
                f"serve[{impl}] request {rid}: {len(streams[rid])} tokens "
                f"streamed, done says {done[rid]}, wanted {sz.new_tokens}")

    events = [json.loads(x) for x in tele.read_text().splitlines() if x]
    by_name: dict[str, dict] = {}
    for e in events:
        if e.get("name"):
            by_name[e["name"]] = e   # the last of each name
    end = by_name.get("serve_end")
    ledger = by_name.get("compile_ledger")
    if end is None or ledger is None:
        raise RuntimeError(
            f"serve[{impl}]: telemetry holds no serve_end/compile_ledger "
            f"event ({sorted(by_name)})")
    if end["rejected"] or end["timed_out"]:
        raise RuntimeError(f"serve[{impl}]: serve_end {end}")
    if not end["prefix_hits"] > 0:
        raise RuntimeError(
            f"serve[{impl}]: prefix cache never hit (serve_end {end})")
    n_tok = sum(len(s) for s in streams.values())
    log(f"serve[{impl}]: {len(done)} requests done, {n_tok} tokens, "
        f"prefix_hits {end['prefix_hits']}, ticks {end['ticks']}, "
        f"warmup/compile {ledger['total_s']:.1f} s "
        f"({json.dumps(ledger['compile_s'])}), wall {wall:.1f} s")
    log(f"serve[{impl}]: {peak_gb()}")
    prompts = {r["id"]: r["prompt_ids"] for r in first + second}
    return {"streams": streams, "prompts": prompts}


def check_logits(sz: ServeSizes, ckpt: Path, runs: dict) -> None:
    """Plain forward, gather read and pallas read against an fp32
    reference, on the tokens the gather server produced for `shared_a`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hyperion_tpu.checkpoint.io import load_gathered
    from hyperion_tpu.infer.generate import model_from_npz
    from hyperion_tpu.models.llama import Llama, init_paged_cache

    params = jax.device_put(load_gathered(ckpt))
    base, cached = model_from_npz(params, sz.max_len)
    assert cached
    prompt = runs["gather"]["prompts"]["shared_a"]
    gen = runs["gather"]["streams"]["shared_a"]
    P, G, bs = len(prompt), len(gen), 16
    MB = -(-(P + G) // bs)
    bt = jnp.arange(1, MB + 1, dtype=jnp.int32)[None]   # blocks 1..MB
    p_ids = jnp.asarray(prompt, jnp.int32)[None]
    g_ids = jnp.asarray(gen, jnp.int32)[None]

    plain = jax.jit(lambda p, x: base.apply({"params": p}, x)[0, -1])(
        params, p_ids)
    plain = np.asarray(plain, np.float32)

    def paged(impl, reference=False):
        """[G, V]: row i is the distribution gen[i] was drawn from —
        the prompt prefilled as one window, the generated tokens
        teacher-forced as a second. `reference`: the same weights in
        fp32 arithmetic (fp32 activations and cache, `highest` matmul
        precision), independent of every bf16 choice under test."""
        cfg = dataclasses.replace(base.cfg, paged_attn_impl=impl)
        if reference:
            cfg = dataclasses.replace(cfg, dtype="float32")
        model = Llama(cfg)

        @jax.jit
        def window(p, cache, ids, index):
            with jax.default_matmul_precision(
                    "highest" if reference else "default"):
                return model.apply({"params": p}, ids, cache=cache,
                                   cache_index=index, block_tables=bt)

        cache = init_paged_cache(cfg, MB + 1, bs)
        pre, cache = window(params, cache, p_ids, jnp.zeros((1,), jnp.int32))
        win, _ = window(params, cache, g_ids, jnp.full((1,), P, jnp.int32))
        return np.concatenate([np.asarray(pre[0, -1:], np.float32),
                               np.asarray(win[0, :-1], np.float32)])

    ref = paged("gather", reference=True)
    lg, lp = paged("gather"), paged("pallas")
    std = float(ref.std())
    rms = {}
    for name, x, r in (("plain", plain, ref[0]), ("gather", lg, ref),
                       ("pallas", lp, ref)):
        if not np.isfinite(x).all():
            raise RuntimeError(f"{name} logits are not finite")
        rms[name] = float(np.sqrt(np.mean((x - r) ** 2)))
        log(f"logits: {name} vs fp32 reference: RMS {rms[name]:.3e} "
            f"({rms[name] / std:.2%} of std {std:.3f}), max "
            f"{float(np.abs(x - r).max()):.3e}")
    log(f"logits: |gather - pallas| max {float(np.abs(lg - lp).max()):.3e} "
        f"over {G} teacher-forced positions; |plain - gather| max "
        f"{float(np.abs(plain - lg[0]).max()):.3e} at the first token")
    for name, e in rms.items():
        if e > NOISE_RMS * std:
            raise RuntimeError(
                f"{name} logits are {e / std:.1%} of std from the fp32 "
                f"reference (held to {NOISE_RMS:.0%})")
    if rms["pallas"] > PALLAS_VS_GATHER * rms["gather"] + 1e-6:
        raise RuntimeError(
            f"pallas read is {rms['pallas'] / rms['gather']:.2f}x as far "
            f"from the reference as gather (held to {PALLAS_VS_GATHER}x)")

    # Token streams, as far as the margin allows. Near-tied logits may
    # flip an argmax between two correct computations, so a token is
    # accepted when its reference logit is near the row's maximum.
    def below(row, tok):
        return float(row.max() - row[tok]) / std

    # the gather server's whole stream (teacher-forced on it, so every
    # position is checkable; position 0 is also the plain forward's)
    worst = max(below(ref[i], tok) for i, tok in enumerate(gen))
    if worst > TOKEN_SLACK:
        raise RuntimeError(
            f"a served token lies {worst:.2f} std below the reference's "
            f"best (held to {TOKEN_SLACK})")
    # the pallas server's stream against the gather server's: equal up
    # to the first flip, which must be a near-tie; past it the contexts
    # differ and nothing more can be said
    other = runs["pallas"]["streams"]["shared_a"]
    same = next((i for i, (a, b) in enumerate(zip(gen, other)) if a != b), G)
    if same < G and below(ref[same], other[same]) > TOKEN_SLACK:
        raise RuntimeError(
            f"pallas stream leaves the gather stream at token {same} by "
            f"{below(ref[same], other[same]):.2f} std, not a near-tie")
    agree = {
        rid: next((i for i, (a, b) in enumerate(zip(s, runs["pallas"][
            "streams"][rid])) if a != b), len(s))
        for rid, s in runs["gather"]["streams"].items()
    }
    log(f"streams: served tokens at most {worst:.3f} std below the "
        f"reference's best; gather and pallas servers agree for "
        f"{json.dumps(agree)} leading tokens of {sz.new_tokens}")


def phase_serve(out: Path, seed: int, rehearse: bool) -> ServeSizes:
    import jax

    from hyperion_tpu.checkpoint.io import export_gathered

    sz = serve_sizes(rehearse)
    c = sz.cfg
    log(f"serve: Llama d_model {c.d_model}, {c.n_heads} heads of "
        f"{c.head_dim} ({c.n_kv_heads} KV), ff {c.ff_dim}, vocab "
        f"{c.vocab_size}, {c.dtype}; depth cut to {c.n_layers} of "
        f"{sz.full_depth} layers; {sz.slots} slots x {sz.max_len} tokens, "
        f"16-token blocks")
    ckpt = out / "llama_smoke.npz"
    t0 = time.perf_counter()
    params = make_weights(c, seed)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    export_gathered(ckpt, params)
    del params
    log(f"serve: {n_bytes / 2**30:.2f} GiB of weights made and exported "
        f"to {ckpt} in {time.perf_counter() - t0:.1f} s")
    settle("weight export")
    runs = {}
    for impl in ("gather", "pallas"):
        runs[impl] = run_server(sz, ckpt, impl, out / f"serve_{impl}", seed)
        settle(f"serve[{impl}]")
    check_logits(sz, ckpt, runs)
    ckpt.unlink()   # gigabytes; everything else in `out` is small
    settle("logit check")
    return sz


# ------------------------------------------------------------------ train


def run_trainer(argv: list[str], base_dir: Path) -> list[float]:
    """One `hyperion_tpu.cli.main` training run; per-epoch losses read
    back from the metrics CSV it wrote."""
    from hyperion_tpu.cli.main import main as cli_main

    argv = [*argv, "--base_dir", str(base_dir)]
    log(f"train: hyperion {' '.join(argv)}")
    t0 = time.perf_counter()
    rc = cli_main(argv)
    if rc != 0:
        raise RuntimeError(f"trainer exited {rc}")
    csvs = sorted((base_dir / "distributed").glob("*_metrics.csv"))
    if len(csvs) != 1:
        raise RuntimeError(f"expected one metrics CSV, found {csvs}")
    with csvs[0].open() as f:
        losses = [float(r["loss"]) for r in csv.DictReader(f)]
    log(f"train: {csvs[0].name}: losses "
        f"{[round(x, 4) for x in losses]} in "
        f"{time.perf_counter() - t0:.1f} s")
    if not losses or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"losses not finite: {losses}")
    # a checkpoint per epoch and the final export: hundreds of MB each
    shutil.rmtree(base_dir / "checkpoints")
    return losses


def train_argv(seed: int, rehearse: bool, steps: int) -> list[str]:
    # one step per epoch: the CSV has a row per epoch, so row 0 is the
    # loss of step 0, before any update
    argv = ["--model", "language_fsdp", "--epochs", str(steps),
            "--steps-per-epoch", "1", "--no-validate", "--seed", str(seed),
            "--lr", "1e-3"]
    if rehearse:
        argv += ["--batch_size", "8", "--seq_len", "32"]
    return argv


def phase_train(out: Path, seed: int, rehearse: bool) -> None:
    steps = 3 if rehearse else 6
    losses = {}
    for tier in ("jit", "jit+pallas"):
        losses[tier] = run_trainer(
            [*train_argv(seed, rehearse, steps), "--compile-tier", tier],
            out / f"train_{tier.replace('+', '_')}")
        if not losses[tier][-1] < losses[tier][0]:
            raise RuntimeError(
                f"{tier}: loss did not fall over {steps} steps: "
                f"{losses[tier]}")
        log(f"train[{tier}]: {peak_gb()}")
        settle(f"train[{tier}]")
    a, b = losses["jit"][0], losses["jit+pallas"][0]
    # same seed, same batch, same init; the tiers differ in kernel
    # arithmetic only (bf16 compute, fp32 loss near ln(vocab) = 10.8)
    if abs(a - b) > 2e-2 * abs(a):
        raise RuntimeError(
            f"step-0 loss differs between tiers: jit {a} vs jit+pallas {b}")
    log(f"train: step-0 loss jit {a:.4f} vs jit+pallas {b:.4f} "
        f"(|diff| {abs(a - b):.2e})")


# ---------------------------------------------------------------- kernels


def phase_kernels(sz: ServeSizes, rehearse: bool) -> None:
    """Each Pallas kernel at the shapes the two phases used must be in
    the compiled program as a `tpu_custom_call` — not the interpreter,
    not an XLA reference."""
    import jax
    import jax.numpy as jnp

    from hyperion_tpu.models.transformer_lm import GPT2_VOCAB_SIZE
    from hyperion_tpu.ops.pallas.flash_attention import flash_attention
    from hyperion_tpu.ops.pallas.fused_ce import fused_softmax_xent
    from hyperion_tpu.ops.pallas.fused_norm import fused_layernorm
    from hyperion_tpu.ops.pallas.paged_attention import paged_attention

    S = jax.ShapeDtypeStruct
    c = sz.cfg
    dt = c.compute_dtype
    bs, MB = 16, -(-sz.max_len // 16)
    pool = S((sz.slots * MB + 1, c.n_kv_heads, bs, c.head_dim), dt)
    bsz, seq = (8, 32) if rehearse else (32, 128)   # the trainer's batch
    lm = S((bsz, seq, 256), jnp.bfloat16)
    vec = S((256,), jnp.float32)
    heads = S((bsz, seq, 4, 64), jnp.bfloat16)

    def paged(B, T):
        return (paged_attention,
                (S((B, T, c.n_heads, c.head_dim), dt), pool, pool,
                 S((B, MB), jnp.int32), S((B,), jnp.int32)))

    def grad_of(f, n):
        return jax.grad(lambda *a: (f(*a).astype(jnp.float32) ** 2).sum(),
                        argnums=tuple(range(n)))

    cases = {
        "paged_attention decode": paged(sz.slots, 1),
        "paged_attention chunk": paged(1, sz.chunk),
        "flash_attention fwd+bwd": (
            grad_of(lambda q, k, v: flash_attention(q, k, v, causal=True), 3),
            (heads, heads, heads)),
        "fused_layernorm fwd+bwd": (
            grad_of(lambda x, r, w, b: fused_layernorm(x, w, b, residual=r), 4),
            (lm, lm, vec, vec)),
        "fused_softmax_xent fwd+bwd": (
            jax.grad(lambda lg, t: fused_softmax_xent(lg, t).mean()),
            (S((bsz * (seq - 1), GPT2_VOCAB_SIZE), jnp.bfloat16),
             S((bsz * (seq - 1),), jnp.int32))),
    }
    for name, (fn, avals) in cases.items():
        text = jax.jit(fn).lower(*avals).compile().as_text()
        held = "tpu_custom_call" in text
        log(f"kernels: {name}: tpu_custom_call "
            f"{'present' if held else 'ABSENT'}")
        if not held and not rehearse:
            raise RuntimeError(
                f"{name} compiled without a tpu_custom_call: the kernel "
                "is not what ran")


# -------------------------------------------------------------- four chips


def phase_four_chips(out: Path, seed: int, rehearse: bool) -> None:
    import jax

    from hyperion_tpu.runtime import comm_check
    from hyperion_tpu.train import trainer
    from hyperion_tpu.utils.memory import live_bytes_in_use

    if comm_check.main([]) != 0:
        raise RuntimeError("comm_check failed")

    # Watch the state the trainer builds on the mesh, at the moment it
    # is built: nothing else is on the devices yet, so what each device
    # holds then is its share of params + optimizer state.
    seen: dict = {}
    build = trainer.create_train_state

    def watching(*a, **kw):
        state, sharding = build(*a, **kw)
        leaves = jax.tree_util.tree_leaves(state)
        seen["total"] = sum(x.nbytes for x in leaves)
        seen["shard"] = {
            d.id: sum(s.data.nbytes for x in leaves
                      for s in x.addressable_shards if s.device == d)
            for d in sharding.mesh.devices.flat}
        seen["stats"] = {d.id: live_bytes_in_use(d)
                         for d in sharding.mesh.devices.flat}
        return state, sharding

    steps = 3
    common = train_argv(seed, rehearse, steps)
    trainer.create_train_state = watching
    try:
        four = run_trainer([*common, "--mesh", "1,4,1,1", "--devices", "4"],
                           out / "train_fsdp4")
    finally:
        trainer.create_train_state = build
    total = seen["total"]
    log(f"four chips: state {total / 2**20:.1f} MiB in all; per device by "
        f"shards {json.dumps({k: round(v / 2**20, 1) for k, v in seen['shard'].items()})} MiB, "
        f"by memory_stats {json.dumps({k: round(v / 2**20, 1) for k, v in seen['stats'].items()})} MiB")
    if len(seen["shard"]) != 4:
        raise RuntimeError(f"mesh holds {len(seen['shard'])} devices, not 4")
    for dev, n in seen["shard"].items():
        # a quarter each, plus the few leaves too small to shard
        if not 0.2 * total <= n <= 0.35 * total:
            raise RuntimeError(
                f"device {dev} holds {n} of {total} state bytes: the "
                "state is not sharded four ways")
    if jax.devices()[0].platform == "tpu":
        for dev, n in seen["stats"].items():
            if not 0.2 * total <= n <= 0.45 * total:
                raise RuntimeError(
                    f"memory_stats: device {dev} held {n} bytes with a "
                    f"{total}-byte state: not a quarter")
    log(f"four chips: {peak_gb()}")
    settle("fsdp x4")
    one = run_trainer([*common, "--mesh", "1,1,1,1", "--devices", "1"],
                      out / "train_fsdp1")
    if abs(four[0] - one[0]) > 1e-2 * abs(one[0]):
        raise RuntimeError(
            f"step-0 loss on four chips {four[0]} vs one {one[0]}")
    for name, losses in (("four", four), ("one", one)):
        if not losses[-1] < losses[0]:
            raise RuntimeError(f"{name}: loss did not fall: {losses}")
    log(f"four chips: step-0 loss 1,4,1,1 mesh {four[0]:.4f} vs one "
        f"device {one[0]:.4f} (|diff| {abs(four[0] - one[0]):.2e}); "
        f"after {steps} steps {four[-1]:.4f} vs {one[-1]:.4f}")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the collectives and the sharded-vs-single "
                        "trainer comparison, on a four-chip host")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes, any backend: walks the control flow "
                        "only, proves nothing about the chip")
    args = p.parse_args(argv)
    if args.rehearse:
        print("REHEARSAL (--rehearse): tiny sizes, TPU not required; this "
              "is not a chip run", flush=True)
        global SETTLE_LIMIT
        SETTLE_LIMIT = 64 << 10

    import jax

    from hyperion_tpu.utils.compile_cache import place_compile_cache

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if dev.platform != "tpu" and not args.rehearse:
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {dev.platform!r} "
            f"({dev.device_kind})")
    if n_dev < args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX found {n_dev}")
    log(f"device: {dev.platform} / {dev.device_kind} x {n_dev}; compile "
        f"cache at {place_compile_cache()}; seed {args.seed}")

    out = REPO / ".chip_smoke" / ("chips4" if args.chips == 4 else "chip1")
    if args.rehearse:
        out = out.with_name(out.name + "_rehearsal")
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(out, args.seed, args.rehearse)
    else:
        sz = phase_serve(out, args.seed, args.rehearse)
        phase_train(out, args.seed, args.rehearse)
        phase_kernels(sz, args.rehearse)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; {peak_gb()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
