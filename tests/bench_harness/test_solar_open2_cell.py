"""The cell `solar2.chat-saturated` and what came with it:
`adapters/serve_solar_open2.py`, `costs_solar_open2.py`,
`reference/solar_open2.py`, the metric `state_bytes_pct`. The cell's
rehearsal itself is a case of `test_bench_harness.py`'s parametrised
tests."""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import solar_open2_faults as faults  # noqa: E402
from benchmarks import costs_solar_open2 as costs, spec  # noqa: E402
from benchmarks.adapters import serve_solar_open2 as adapter  # noqa: E402
from benchmarks.readers import counter_ratio  # noqa: E402
from benchmarks.reference import solar_open2 as reference  # noqa: E402

CELL = "solar2.chat-saturated"
CONFIG = "solar-open2-250b-l4-ep8"
# the published-model catalog (a JSON line a model), where one is at hand
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG")
# the serving metrics that move `out_tok_per_s`, but for the flight
# counters' three (the harness holds them together, and one of them
# moves `gap_p99_ms`, which this cell does not report: PERF.md section
# 7), and the cell's own
REPORTED = {"tick_host_ms", "batch_occupancy_pct", "kv_fill_pct",
            "decode_tick_ms", "gap_p50_ms", "tick_hbm_roofline_pct",
            "device_idle_pct.serve", "expert_picks_per_tick",
            "experts_touched_pct", "expert_tokens_per_touched",
            "state_bytes_pct"}


def test_costs_from_shapes():
    """Hand arithmetic from the shapes: the parameters by part, the state
    of a row, and a tick's least bytes at the traffic's fill."""
    m = spec.cell(CELL)["model"]
    s = costs.solar_sizes(m)
    assert s["kda_params"] == 137_732_288
    assert s["gqa_params"] == 109_051_904
    assert s["expert_params"] == 15_728_640        # the shared one's too
    assert s["params"] == 3 * 137_732_288 + 109_051_904 + 4 * (
        15_728_640 + 1_310_720 + 320 + 40 * 15_728_640) \
        + 201_326_592 + 36_864 == 3_308_353_344
    assert (s["expert_layers"], s["experts_held"], s["recurrent_layers"]) \
        == (4, 40, 3)
    # a slot's state in a layer: S 64 x 128 x 128 float32, the conv's
    # last three inputs of q, k and v in bf16
    assert s["state_row_bytes"] == 4 * 2 ** 20 + 3 * 3 * 8192 * 2
    # one GQA layer, 8 KV heads of 128: 4 KiB a position
    assert s["kv_bytes_per_token"] == 4096
    assert 1.38e9 < s["tick_fixed_bytes"] < 1.39e9
    # 128 live slots of 2.2k positions, 96 % of the 160 held matrices
    # touched, 128 rows x 3 layers advanced
    need = costs.tick_bytes(m, 128 * 2200, 0.96 * 160, 384)
    assert 10.6e9 < need < 10.8e9              # 13 ms at 819 GB/s
    share = costs.state_bytes(m, 384) / need
    assert costs.state_bytes(m, 384) == 2 * 384 * s["state_row_bytes"]
    assert 0.31 < share < 0.32


def check_benchmark(bench: dict) -> None:
    """What this cell holds of BENCHMARK.json, each entry found by its
    name: a later PR appends configurations, cells and metrics."""
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == CONFIG and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert {x["name"] for x in spec.reported_in(bench, "per_layer", CELL)} \
        == REPORTED
    assert {x["name"] for x in spec.reported_in(bench, "end_to_end", CELL)} \
        == {"out_tok_per_s", "setup_s"}
    assert next(x for x in bench["per_layer"]
                if x["name"] == "state_bytes_pct") == {
        "name": "state_bytes_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "serve model step",
        "moves": "out_tok_per_s", "workloads": [CELL]}


def test_the_configuration_is_the_catalogs_row():
    m = spec.cell(CELL)["model"]
    if CATALOG and Path(CATALOG).exists():
        row = next(r for r in map(json.loads, Path(CATALOG).open())
                   if r["name"] == "Solar-Open2-250B")
        assert m["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if m.get(k) != v}
        assert changed == set(m["published"])
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == m["source"]
    # every published width as published
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["linear_attn_config"]) == (
        4096, 64, 8, 128, 1280, 8,
        {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
         "num_kv_heads": None})
    assert m["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608, "max_position_embeddings": 1048576}
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"],
            m["max_position_embeddings"]) == (4, 40, 24576, 8192)
    assert (m["router_experts"], m["experts_held"], m["layers_kept"]) == \
        (320, [0, 40], [0, 1, 2, 3])
    assert {"published", "reduced_why", "assumed", "stands_for",
            "weights", "runs_through", "rehearse"} <= set(m)
    assumed = " ".join(m["assumed"])
    for what in ("sigmoid scores", "elementwise", "no QK-norm",
                 "float32"):
        assert what in assumed, what
    assert "8-chip stage" in m["stands_for"]
    cfg = adapter.model_config(m)
    assert cfg.layer_types == ("full", "recurrent", "recurrent", "recurrent")
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.gate_rank, cfg.conv_size,
            cfg.n_experts, cfg.experts_held, cfg.route_scale) == \
        (64, 128, 128, 4, 320, (0, 40), 1.0)


def test_the_cell_as_configured():
    check_benchmark(spec.benchmark())
    cell = spec.cell(CELL)
    assert cell["generator"] == "closed_loop"
    assert cell["adapter"] == "serve_solar_open2"
    assert cell["traffic"] == {
        "clients": 256, "grid": 512, "strata": 32,
        "prompt": {"median": 1020, "sigma": 0.8, "min": 64, "max": 4096},
        "output": {"median": 129, "sigma": 0.6, "min": 16, "max": 1024}}
    # every other field at its default: reserve admission
    assert cell["engine"] == {
        "slots": 128, "max_len": 8192, "block_size": 16,
        "prefill_chunk": 512, "prefix_cache": False, "min_bucket": 64,
        "queue_capacity": 256}
    assert cell["check"]["shortest"] == 2 \
        and cell["check"]["long_over"] == 4096
    assert cell["check"]["near_rows"] <= 3 * cell["traffic"]["output"]["min"]
    assert cell["trace_s"] == 3.0


def test_the_traffic_has_the_azure_conversation_medians():
    """Prompts and outputs at the conversation trace's medians; contexts
    under a slot's 8192 positions; a few of them past `long_over`, so
    the check finds its long request; every checked request brings
    rows enough for the near-tie term."""
    from benchmarks.traffic.lengths import Lengths

    cell = spec.cell(CELL)
    lengths = Lengths(cell["traffic"], 3)
    pairs = np.asarray([lengths.next()[1:] for _ in range(512)])
    prompts, outputs = pairs[:, 0], pairs[:, 1]
    assert np.median(prompts) == 1020 and np.median(outputs) == 129
    assert (prompts + outputs).max() <= 8192
    assert prompts.min() >= 64 and outputs.min() >= 16
    assert 0.03 < np.mean(prompts + outputs > cell["check"]["long_over"]) \
        < 0.06
    # every admission runs 1-8 pieces of 512
    assert set(np.unique(-(-prompts // 512))) <= set(range(1, 9))
    assert 3 * outputs.min() >= cell["check"]["near_rows"]


def test_the_new_metric_reads_the_state_share():
    entry = next(x for x in spec.cell(CELL)["per_layer"]
                 if x["name"] == "state_bytes_pct")
    assert entry["reader"] == "counter_ratio"
    ticks = [{"state_bytes": 3.0e9, "least_bytes": 10.0e9},
             {"state_bytes": 1.0e9, "least_bytes": 6.0e9}]
    assert counter_ratio.read({"counted": ticks}, **entry["args"]) == \
        pytest.approx(25.0)
    # a program whose records count no recurrent rows: left out
    assert counter_ratio.read({"counted": [{"step_us": 5}]},
                              **entry["args"]) is None


# ------------------------------------------------- the check and its faults


def rehearsal():
    cell = spec.cell(CELL)
    m = {**cell["model"], **cell["model"]["rehearse"],
         "linear_attn_config": {**cell["model"]["linear_attn_config"],
                                **cell["model"]["rehearse"][
                                    "linear_attn_config"]}}
    return m, {**cell["check"], **cell["rehearse"]["check"]}


# prompts in the bucket of 8 (pad positions); a prompt of ten chunks of
# 4 while the other slots decode, whose context passes `long_over`: the
# long request the check reads
SHAPES = [(5, 14), (6, 14), (40, 12), (3, 20)]
CHUNK = 4


def serve(model, params, ids):
    """The served tokens of the stand-in: the engine over `model`."""
    from hyperion_tpu.serve.engine import Engine, EngineConfig
    from hyperion_tpu.serve.queue import Request

    eng = Engine(model, {"params": params}, EngineConfig(
        slots=3, max_len=64, block_size=4, prefill_chunk=CHUNK,
        prefix_cache=False))
    reqs = [Request(prompt_ids=p, max_new_tokens=g, id=f"r{i}")
            for i, (p, (_, g)) in enumerate(zip(ids, SHAPES))]
    for r in reqs:
        eng.submit(r)
    while not eng.idle:
        eng.step()
    return [{"prompt": r.prompt_ids, "tokens": list(r.tokens)} for r in reqs]


@pytest.fixture(scope="module")
def stand_in():
    """The rehearsal's configuration, weights large enough that the
    argmax is decided by more than rounding, and the requests the sound
    system serves."""
    m, check = rehearsal()
    cls = faults.twin("sound")
    model = cls(adapter.model_config(m))
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim < 2 or path[-1].key == "conv"
        else 14 * w, adapter.solar_weights(model, 7))
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, m["vocab_size"], n).astype(np.int32)
           for n, _ in SHAPES]
    return m, check, cls, params, ids, serve(model, params, ids)


def test_plain_reference_agrees_with_models_solar_open2(stand_in):
    m, _, cls, params, ids, done = stand_in
    seq = jnp.asarray(np.concatenate(
        [done[2]["prompt"], done[2]["tokens"]])[None])
    got = jax.jit(cls(adapter.model_config(m)).apply)({"params": params}, seq)
    want = reference.logits(params, seq, **reference.settings(m))
    assert float(want.std()) > 0.3
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(want.std())


def test_the_check_takes_the_shortest_and_one_long_request(stand_in):
    m, check, _, params, _, done = stand_in
    said = {}
    z, margin = adapter.reference_slack(
        params, m, done, check, lambda **kw: said.update(kw))
    assert said["reference_contexts"] == [19, 20, 52]       # 52 > 24
    assert z.shape == (40,) and z.max() < 1e-3     # float32: its own tokens
    assert adapter.agrees(z, margin, check["near_rows"])
    # fewer checked rows than the near-tie term may read: not correct
    assert not adapter.agrees(z, margin, 41)
    # no finished request past `long_over`: the check cannot pass
    assert adapter.reference_slack(
        params, m, done[:2], check, lambda **kw: None) is None


@pytest.mark.parametrize("fault", sorted(faults.SYSTEM))
def test_a_fault_in_the_system_fails_the_check(stand_in, fault):
    """The stand-in for the system served with one fault planted, held
    to the sound reference through the expression that decides
    `correct` (40 checked tokens here; what the chip reads at the cell's
    size is in PERF.md section 6)."""
    m, check, _, params, ids, sound = stand_in
    with faults.system(fault):
        done = serve(faults.twin(fault)(adapter.model_config(m)), params,
                     ids)
    assert [d["tokens"] for d in done] != [d["tokens"] for d in sound]
    checked = adapter.reference_slack(params, m, done, check,
                                      lambda **kw: None)
    assert not adapter.agrees(*checked, check["near_rows"]), \
        adapter.slack_readings(*checked, check["near_rows"])


def test_the_system_in_fp8_fails_the_check(stand_in):
    """The precision below the configuration's in the system's place:
    the stand-in served with every matrix in float8, held to the
    reference of the bf16 weights."""
    m, check, cls, params, ids, sound = stand_in
    done = serve(cls(adapter.model_config(m)), faults.fp8(params), ids)
    assert [d["tokens"] for d in done] != [d["tokens"] for d in sound]
    checked = adapter.reference_slack(params, m, done, check,
                                      lambda **kw: None)
    assert not adapter.agrees(*checked, check["near_rows"]), \
        adapter.slack_readings(*checked, check["near_rows"])


@pytest.mark.parametrize("fault", sorted(faults.REFERENCE))
def test_a_fault_in_the_reference_fails_the_check(stand_in, fault):
    """The sound system's tokens against the reference put wrong in one
    way: what the chip reads of each at the cell's size."""
    m, check, _, params, _, done = stand_in
    with faults.reference(fault):
        checked = adapter.reference_slack(params, m, done, check,
                                          lambda **kw: None)
    assert not adapter.agrees(*checked, check["near_rows"])
    assert adapter.agrees(*adapter.reference_slack(
        params, m, done, check, lambda **kw: None), check["near_rows"])


@pytest.mark.parametrize("case, z, margin, ok", [
    # what the chip reads of the system: bf16 served, the near-ties
    # 0.022-0.026 under the reference's best on average, all rows
    # 0.013-0.015, the worst token 0.53 at most
    ("the system", [0.0] * 700 + [0.06] * 200 + [0.53],
     [0.05] * 300 + [0.5] * 601, True),
    ("fewer rows than the near-tie term may read", [0.0] * 99, [0.05] * 99,
     False),
    # each term either side of its limit, the others well inside theirs
    ("under the near-tie limit", [0.09] * 300 + [0.0] * 600,
     [0.05] * 300 + [0.5] * 600, True),
    ("over the near-tie limit", [0.11] * 300 + [0.0] * 600,
     [0.05] * 300 + [0.5] * 600, False),
    ("under the mean's limit", [0.0] * 100 + [0.088] * 800,
     [0.05] * 100 + [0.5] * 800, True),
    ("over the mean's limit", [0.0] * 100 + [0.095] * 800,
     [0.05] * 100 + [0.5] * 800, False),
    ("one token under the worst limit", [0.0] * 899 + [1.45], [0.3] * 900,
     True),
    ("one token past it", [0.0] * 899 + [1.6], [0.3] * 900, False),
])
def test_what_the_three_limits_separate(case, z, margin, ok):
    assert adapter.agrees(np.asarray(z), np.asarray(margin), 100) is ok, case
    assert (adapter.NEAR_MEAN_SLACK, adapter.MEAN_SLACK,
            adapter.WORST_SLACK) == (0.1, 0.08, 1.5)
