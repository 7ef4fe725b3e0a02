"""The benchmark's own tests (BENCHMARK.json `paths`): the harness under
`benchmarks/` rehearsed end to end at tiny sizes on the CPU, its
arithmetic on hand-made inputs, its generators, its trace reduction on
a hand-encoded `.xplane.pb`, and its plain references against the
models they stand beside. Seconds, not minutes; nothing here is a
measurement."""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import costs, spec, stats, trace_reduce  # noqa: E402
from benchmarks.traffic import closed_loop, lengths  # noqa: E402

SERVE, TRAIN = "mistral7b.chat-saturated", "gpt2s.pretrain-1k"


def bench(root: Path, cache: Path, *args: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache), "BENCH_RUN": "ignored"}
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


_RUNS: dict = {}


def rehearse(cache, name: str, trace: int, seed: str = "3000000019"):
    """One rehearsal per (cell, trace) and module, however many tests
    look at it."""
    if (name, trace) not in _RUNS:
        _RUNS[name, trace] = bench(
            ROOT, cache, "--workload", name, "--seed", seed, "--seconds",
            "1.5", "--trace", str(trace), "--rehearse")
    return _RUNS[name, trace]


@pytest.fixture(scope="module")
def serve_run(cache):
    return rehearse(cache, SERVE, 0)


@pytest.fixture(scope="module")
def train_traced_run(cache):
    return rehearse(cache, TRAIN, 1)


# ---------------------------------------------------------------- end to end


@pytest.mark.parametrize(
    "name", [w["name"] for w in spec.benchmark()["workloads"]])
def test_every_cell_rehearses_and_prints_the_contract_line(cache, name):
    """Also the cells later PRs add: a cell is data, and data is checked."""
    proc = rehearse(cache, name, 0)
    out = last_line(proc)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["attempted"] > 0
    assert out["failed"] == 0
    assert set(out["metrics"]) == \
        {m["name"] for m in spec.cell(name)["end_to_end"]}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # a rehearsal reports the platform it ran on: never a TPU's
    assert out["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert proc.stdout.startswith("REHEARSAL")


def test_serve_rehearsal_prints_the_knee_on_an_earlier_line(serve_run):
    infos = [json.loads(x)["info"] for x in serve_run.stdout.splitlines()
             if x.startswith('{"info"')]
    rate = [i for i in infos if "completed_requests_per_s" in i]
    assert rate and rate[0]["completed_requests_per_s"] > 0
    # what the traffic fills of the pool stands beside the peak
    kv = last_line(serve_run)["kv"]
    assert 0 < kv["live_bytes_mean"] <= kv["live_bytes_peak"] <= kv["pool_bytes"]
    assert rate[0]["kv"] == kv and sum(rate[0]["tokens_by_fifth"]) == \
        rate[0]["tokens_in_window"]
    checks = [i for i in infos if "token_slack_std" in i][0]
    assert checks["no_compile_in_window"] and checks["lost"] == 0
    assert checks["token_slack_std"] <= 0.6


def test_train_traced_rehearsal_reports_the_per_layer_metrics(train_traced_run):
    out = last_line(train_traced_run)
    assert out["correct"] is True and out["attempted"] > 0
    # host-clock metrics are there; the trace-fed ones find no device
    # plane on a CPU, their readers return nothing, and they are left out
    assert set(out["metrics"]) == {"step_ms", "input_wait_ms"}
    assert "setup_s" not in out["metrics"]
    assert "busy_s" not in out["device"]
    # `correct` rests on the comparison with the plain reference after
    # the window, and every run prints what it read
    check = [json.loads(x)["info"] for x in train_traced_run.stdout.splitlines()
             if '"logits_error"' in x][0]
    assert 0 < check["logits_error"] < 0.05 and check["loss_gap"] < 0.01
    assert check["least_cosine"] > 0.8
    assert check["check_losses"][-1] < check["check_losses"][0]


def test_refuses_to_run_without_a_tpu(cache):
    proc = bench(ROOT, cache, "--workload", TRAIN, "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_new_cell_generator_metric_and_reader_are_found_as_new_files(
        tmp_path, cache):
    """What a later PR does: new files and new entries, no edit of a
    file. The cell it adds has a generator of its own (half the clients
    wait a moment before they send again)."""
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "hyperion_tpu").symlink_to(ROOT / "hyperion_tpu")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"].append({
        "name": "mistral7b.added", "config": "mistral-7b-v0.2-l16",
        "traffic": "added", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if SERVE in m.get("workloads", []):
            m["workloads"].append("mistral7b.added")
    for name in ("gap_p90_ms", "answered"):
        b["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "serve model step",
            "moves": "gap_p99_ms", "workloads": ["mistral7b.added"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    here = tmp_path / "benchmarks"
    cell = json.loads((here / "workloads" / f"{SERVE}.json").read_text())
    cell["generator"] = "thinking_clients"
    cell["rehearse"]["traffic"]["think_s"] = 0.01
    (here / "workloads" / "mistral7b.added.json").write_text(json.dumps(cell))
    (here / "traffic" / "thinking_clients.py").write_text(
        "from benchmarks.traffic import closed_loop\n\n\n"
        "class Source(closed_loop.Source):\n"
        "    def __init__(self, params, seed, vocab):\n"
        "        super().__init__(params, seed, vocab)\n"
        "        self.think_s = params['think_s']\n\n"
        "    def done(self, t):\n"
        "        super().done(t + self.think_s)\n\n"
        "    def pop_due(self, t, room):\n"
        "        ready = sum(x <= t for x in self.free_since)\n"
        "        return super().pop_due(t, min(room, ready))\n")
    (here / "metrics" / "gap_p90_ms.json").write_text(json.dumps(
        {"reader": "series_quantile",
         "args": {"series": "gap_s", "q": 90, "scale": 1000}}))
    (here / "metrics" / "answered.json").write_text(
        json.dumps({"reader": "count_answered"}))
    (here / "readers" / "count_answered.py").write_text(
        "def read(ctx):\n    return float(sum(r['first_token'] is not None"
        " for r in ctx['requests']))\n")
    args = ("--workload", "mistral7b.added", "--seed", "7", "--seconds", "1",
            "--rehearse")
    out = last_line(bench(tmp_path, cache, *args, "--trace", "0"))
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "gap_p99_ms", "setup_s"}
    out = last_line(bench(tmp_path, cache, *args, "--trace", "1"))
    assert set(out["metrics"]) == {"gap_p90_ms", "answered"}
    assert out["metrics"]["answered"]["value"] >= out["attempted"]
    assert 0 < out["metrics"]["gap_p90_ms"]["value"] < 500


# ---------------------------------------------------------------- data files


def test_every_name_in_benchmark_json_has_its_files():
    b = spec.benchmark()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        cell = spec.cell(w["name"])
        assert (ROOT / "benchmarks" / "adapters"
                / f"{cell['adapter']}.py").exists()
        assert {m["name"] for m in cell["end_to_end"]} > {"setup_s"}
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert callable(spec.plugin("readers", m["reader"]).read)
            # the metric it should move is reported in this cell
            assert w["name"] in e2e[m["moves"]].get("workloads", cells)
    for c in b["configs"]:
        held = json.loads((ROOT / c["file"]).read_text())
        assert held["source"] == c["source"]
        assert set(c["reduced"]) == set(held.get("published", {}))


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError, match="no peaks for device kind"):
        spec.peaks("TPU v9 imaginary")


def test_costs_from_shapes():
    m = spec.cell(SERVE)["model"]
    s = costs.decoder_sizes(m)
    assert s["layer_params"] == 218_103_808
    assert abs(s["params"] - 3.752e9) < 1e6
    assert s["kv_bytes_per_token"] == 64 * 1024
    assert costs.tick_bytes(m, 1000) == \
        s["tick_weight_bytes"] + 1000 * 65536
    g = spec.cell(TRAIN)["model"]
    # 6 x 123.5 M matmul parameters + causal attention at 1024
    assert abs(costs.gpt_train_flops_per_token(g, 1024) - 7.98e8) < 2e6


# ---------------------------------------------------------------- arithmetic


def test_percentile_interpolates_and_is_none_of_nothing():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 95) == 95
    assert stats.percentile([], 95) is None


def test_gaps_count_where_they_end():
    times = [0.0, 1.0, 1.5, 3.0, 9.0]
    # the gap 1.0 -> 1.5 and 1.5 -> 3.0 end inside [1.2, 3.0]; the first
    # token ends no gap; 0.0 -> 1.0 ends before, 3.0 -> 9.0 after
    assert stats.gaps_ending_in(times, 1.2, 3.0) == [0.5, 1.5]
    assert stats.gaps_ending_in([2.0], 0, 10) == []
    assert stats.tokens_in(times, 1.0, 3.0) == 3


# ---------------------------------------------------------------- generators

CHAT = {"grid": 64, "strata": 8,
        "prompt": {"median": 1020, "sigma": 0.8, "min": 16, "max": 1536},
        "output": {"median": 129, "sigma": 0.6, "min": 16, "max": 512}}


def test_lengths_are_one_set_for_every_seed_in_rounds_that_are_fair_samples():
    prompts = lengths.quantiles(CHAT["prompt"], 64)
    outputs = lengths.quantiles(CHAT["output"], 64)
    assert prompts.min() >= 16 and prompts.max() == 1536
    assert abs(np.median(prompts) - 1020) < 32
    assert abs(np.median(outputs) - 129) < 4

    def one_pass(seed, params=CHAT):
        src = lengths.Lengths(params, seed)
        return np.array([src.next()[1:] for _ in range(64)])

    a, b = one_pass(5), one_pass(3_000_000_019)
    assert (a == one_pass(5)).all() and (a != b).any()
    for x in (a, b):
        # the same prompts and the same outputs, each once a pass
        assert sorted(x[:, 0]) == sorted(prompts)
        assert sorted(x[:, 1]) == sorted(outputs)
        # a round of 8 takes one prompt and one output from each band of
        # 8 neighbouring quantiles
        for r in range(8):
            for col, values in ((0, prompts), (1, outputs)):
                bands = [set(values[8 * j:8 * j + 8]) for j in range(8)]
                got = sorted(x[8 * r:8 * r + 8, col])
                assert all(v in band for v, band in zip(got, bands))
    # the pairing is the seed's: prompts and outputs are independent
    assert (a[np.argsort(a[:, 0]), 1] != b[np.argsort(b[:, 0]), 1]).any()
    with pytest.raises(ValueError, match="does not divide"):
        lengths.Lengths({**CHAT, "strata": 7}, 1)
    assert sorted(one_pass(5, {**CHAT, "strata": 1})[:, 0]) == sorted(prompts)
    assert (lengths.prompt_ids(5, 0, 9, 100) ==
            lengths.prompt_ids(5, 0, 9, 100)).all()


def test_closed_loop_keeps_its_clients_and_is_a_function_of_the_seed():
    params = {**CHAT, "clients": 6}

    def play(seed):
        src = closed_loop.Source(params, seed, vocab=100)
        first = src.pop_due(0.0, room=4)
        assert len(first) == 4                  # the queue's room, not more
        assert src.pop_due(0.0, room=0) == []
        rest = src.pop_due(0.1, room=2)
        assert len(rest) == 2 and src.free == 0
        assert not src.window_may_open(0.1, active=2, slots=3)
        assert src.window_may_open(0.2, active=3, slots=3)
        src.done(0.7)                           # a client is free again
        nxt = src.pop_due(0.8, room=4)
        assert len(nxt) == 1 and nxt[0]["due"] == 0.7
        return [(len(r["prompt"]), r["max_new"], int(r["prompt"][0]))
                for r in first + rest + nxt]

    assert play(11) == play(11) != play(12)


# ---------------------------------------------------------------- the trace


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def xplane(name: str, lines: dict) -> bytes:
    """An XPlane message: {line name: [(event name, start_ns, dur_ns)]}."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = _field(2, name)
    for k, (line, evs) in enumerate(lines.items()):
        msg = _field(1, k + 1) + _field(2, line)
        for n, start, dur in evs:
            msg += _field(4, _field(1, ids[n]) + _field(2, start * 1000)
                          + _field(3, dur * 1000))
        body += _field(3, msg)
    for n, i in ids.items():
        body += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    return _field(1, body)


@pytest.fixture(scope="module")
def synthetic_profile(tmp_path_factory):
    """1 ms window. Device ops: [100,300) and an overlapping [250,400),
    then [600,900) us: busy 600 us, idle 400 us in three gaps."""
    us = 1000
    dev = xplane("/device:TPU:0", {
        "XLA Ops": [("%fusion.1 = f32[48,32]{1,0:T(8,128)} fusion(f32[8]{0} %p)",
                     100 * us, 200 * us),
                    ("copy.2", 250 * us, 150 * us),
                    ("%fusion.17 = f32[48,32]{1,0:T(8,128)} fusion(f32[8]{0} %q)",
                     600 * us, 300 * us)],
        "XLA Modules": [("jit__tick_impl(123)", 100 * us, 300 * us),
                        ("jit__tick_impl(123)", 600 * us, 300 * us),
                        ("jit_other(9)", 2000 * us, 50 * us)],
        "Steps": [("0", 0, 1000 * us)]})
    host = xplane("/host:CPU", {
        "python": [("bench.window", 0, 1000 * us),
                   ("bench.step:0", 0, 500 * us),
                   ("bench.step:1", 500 * us, 450 * us),
                   ("PjitFunction(f)", 10 * us, 20 * us)]})
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(dev + host)
    return trace_reduce.load(trace_reduce.newest_xplane(d.parents[2]))


def test_trace_busy_is_the_union_and_idle_the_rest(synthetic_profile):
    r = trace_reduce.reduce(synthetic_profile)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["busy_s"] == pytest.approx(600e-6)     # not 650: overlap once
    from benchmarks.readers import device_idle

    assert device_idle.read({"trace": r}) == pytest.approx(40.0)
    assert device_idle.read({"trace": None}) is None


def test_trace_top_ops_and_program_runs(synthetic_profile):
    r = trace_reduce.reduce(synthetic_profile, top=2)
    # the whole HLO line is cut to name and shape, numbers dropped
    assert r["device_ops"][0] == ["%fusion f32[48,32]", pytest.approx(500e-6)]
    assert r["device_ops"][1] == ["copy.2", pytest.approx(150e-6)]
    # runs of a program inside the window; the one outside is left out
    assert r["modules"] == {"jit__tick_impl(123)":
                            [pytest.approx(300e-6), pytest.approx(300e-6)]}
    from benchmarks.readers import tick_hbm_roofline

    ctx = {"trace": r, "tick_bytes": 819e9 * 150e-6,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert tick_hbm_roofline.read(ctx) == pytest.approx(50.0)
    assert tick_hbm_roofline.read({**ctx, "trace": {**r, "modules": {}}}) is None


def test_trace_names_idle_gaps_by_what_the_host_had_open(synthetic_profile):
    # idle: [0,100) under step 0, [400,600) under steps 0 and 1, and
    # [900,1000), of which step 1 (over at 950) covers half
    plain = dict(trace_reduce.reduce(synthetic_profile)["idle_gaps"])
    assert plain == {"bench.step": pytest.approx(350e-6),
                     "(no span)": pytest.approx(50e-6)}
    segs = {0: [("admit", 200e-6), ("device", 300e-6)],
            1: [("queue_pop", 30e-6), ("device", 300e-6)]}
    named = dict(trace_reduce.reduce(
        synthetic_profile, step_segments=segs)["idle_gaps"])
    assert named == {"bench.step/admit": pytest.approx(100e-6),
                     "bench.step/device": pytest.approx(170e-6),
                     "bench.step/queue_pop": pytest.approx(30e-6),
                     "bench.step/other": pytest.approx(50e-6),
                     "(no span)": pytest.approx(50e-6)}


def test_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    d = tmp_path / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        xplane("/host:CPU", {"python": [("bench.window", 0, 1000)]}))
    assert trace_reduce.reduce(trace_reduce.load(
        trace_reduce.newest_xplane(tmp_path))) is None
    with pytest.raises(FileNotFoundError):
        trace_reduce.newest_xplane(tmp_path / "plugins")


# ---------------------------------------------------------------- references


def test_plain_decoder_agrees_with_llama_apply():
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters.serve import model_config
    from benchmarks.reference import decoder
    from benchmarks.weights import decoder_weights
    from hyperion_tpu.models.llama import Llama

    m = {**spec.cell(SERVE)["model"]}
    m.update(m.pop("rehearse"))
    model = Llama(model_config(m))
    params = decoder_weights(model, 3_000_000_019)
    ids = jax.random.randint(jax.random.key(1), (2, 24), 0, m["vocab_size"])
    ref = decoder.logits(params, ids, n_layers=m["num_hidden_layers"],
                         theta=m["rope_theta"], eps=m["rms_norm_eps"])
    got = model.apply({"params": params}, ids)
    # float32 both ways: what is left is the order of the sums
    assert jnp.abs(ref - got).max() < 2e-5 * float(jnp.abs(ref).max() + 1)
    # a token that is its row's best has no slack; another has some
    p, g = 8, 5
    seq = np.asarray(ids[0]).copy()
    seq[p:p + g] = np.asarray(jnp.argmax(ref[0, p - 1:p - 1 + g], -1))
    assert decoder.token_slack(np.asarray(ref), [(p, g, seq)]) == 0.0
    seq[p + 2] = (seq[p + 2] + 1) % m["vocab_size"]
    assert decoder.token_slack(np.asarray(ref), [(p, g, seq)]) > 0


@pytest.fixture(scope="module")
def tiny_gpt():
    """(model, parameters away from their initial values, ids, system
    loss): biases and scales moved from 0 and 1, so that a reference
    that dropped one would show."""
    import jax

    from hyperion_tpu.models.transformer_lm import TransformerLM, gpt2_lm_config
    from hyperion_tpu.train.losses import next_token_loss

    model = TransformerLM(gpt2_lm_config(
        vocab_size=512, d_model=64, n_heads=4, n_layers=2, ff_dim=256,
        max_len=32))
    params = model.init_params(jax.random.key(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    ids = jax.random.randint(jax.random.key(5), (4, 32), 0, 512)

    def system_loss(p, ids):
        return next_token_loss(model.apply({"params": p}, ids), ids)

    return model, params, ids, system_loss


OPT = {"learning_rate": 1e-3, "weight_decay": 0.01, "grad_clip_norm": 0.5}


def system_steps(system_loss, params, ids, n, grads=lambda g: g, skip=False):
    """n steps of the system's optimizer on one batch, as
    `make_train_step` takes them; `grads` and `skip` put a fault in."""
    import jax
    import optax

    from hyperion_tpu.train.state import make_optimizer

    opt = make_optimizer(**OPT)
    state, losses = opt.init(params), []
    for _ in range(n):
        loss, g = jax.value_and_grad(system_loss)(params, ids)
        losses.append(float(loss))
        if not skip:
            updates, state = opt.update(grads(g), state, params)
            params = optax.apply_updates(params, updates)
    return losses, params


def test_plain_gpt_and_its_adamw_agree_with_the_system(tiny_gpt):
    from benchmarks.adapters import train
    from benchmarks.reference import gpt

    model, params, ids, system_loss = tiny_gpt
    want = system_loss(params, ids)
    assert abs(float(gpt.loss(params, ids)) - float(want)) < 1e-5
    assert abs(float(want) - math.log(512)) < 1.0
    err = gpt.logits_error(model.apply({"params": params}, ids),
                           gpt.logits(params, ids))
    assert err < 1e-5
    got_losses, got = system_steps(system_loss, params, ids, 6)
    ref_losses, ref = gpt.train(params, [ids] * 6, **OPT)
    assert np.abs(np.subtract(got_losses, ref_losses)).max() < 1e-5
    assert got_losses[-1] < got_losses[0] - 0.3
    cosines = gpt.update_cosines(params, got, ref)
    assert "block_1/attn" in cosines and min(cosines.values()) > 0.999
    assert train.agrees(err, 1e-5, min(cosines.values()))


def _zero(tree, word):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * 0 if word in jax.tree_util.keystr(path) else x,
        tree)


@pytest.mark.parametrize("fault", [
    "no_mask", "no_mlp", "weights_fp8", "skipped_update",
    "attention_gradients_zero", "half_the_learning_rate"])
def test_a_broken_system_fails_the_training_check(tiny_gpt, fault):
    """The check after the training window has to fail where a later PR
    breaks the forward pass, the backward pass or the update: each fault
    is put into a stand-in for the system, and the comparison with the
    plain reference says no under the adapter's own slacks."""
    import jax.numpy as jnp

    from benchmarks.adapters import train
    from benchmarks.reference import gpt

    model, params, ids, system_loss = tiny_gpt
    want_logits = gpt.logits(params, ids)
    want_losses, want = gpt.train(params, [ids] * train.CHECK_STEPS, **OPT)
    logits, kw = model.apply({"params": params}, ids), {}
    if fault == "no_mask":
        logits = gpt.logits(params, ids, causal=False)
    elif fault == "no_mlp":
        logits = model.apply({"params": _zero(params, "fc2")}, ids)
    elif fault == "weights_fp8":
        import jax

        logits = model.apply({"params": jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), params)}, ids)
    elif fault == "skipped_update":
        kw = {"skip": True}
    elif fault == "attention_gradients_zero":
        kw = {"grads": lambda g: _zero(g, "attn")}
    elif fault == "half_the_learning_rate":
        kw = {"grads": lambda g: g}
        want_losses, want = gpt.train(
            params, [ids] * train.CHECK_STEPS,
            **{**OPT, "learning_rate": 2 * OPT["learning_rate"]})
    losses, got = system_steps(system_loss, params, ids, train.CHECK_STEPS, **kw)
    numbers = {
        "logits_error": gpt.logits_error(logits, want_logits),
        "loss_gap": max(abs(a - b) for a, b in zip(losses, want_losses)),
        "least_cosine": min(gpt.update_cosines(params, got, want).values())}
    assert not train.agrees(**numbers), numbers
