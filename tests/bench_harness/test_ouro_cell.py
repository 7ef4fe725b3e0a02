"""The cell `ouro.shortchat-saturated` and what PR 34 brought with it:
`adapters/serve_ouro.py`, `costs_ouro.py`, `reference/ouro.py`, the
metric `tick_ms_per_layer_pass`. The cell's rehearsal itself is a case
of `test_bench_harness.py`'s parametrised test."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from ouro_faults import (  # noqa: E402
    FAULTS, another_slots_token, fault as wrong_reference)
from benchmarks import costs_ouro, spec  # noqa: E402
from benchmarks.adapters import serve_ouro  # noqa: E402
from benchmarks.readers import series_quantile  # noqa: E402
from benchmarks.reference import ouro as reference  # noqa: E402

CELL = "ouro.shortchat-saturated"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_costs_from_shapes():
    """ISSUE 34's hand arithmetic."""
    m = spec.cell(CELL)["model"]
    s = costs_ouro.ouro_sizes(m)
    # attention 16,777,216 + MLP 34,603,008 + norms 8,192 a layer
    assert s["layer_params"] == 16_777_216 + 34_603_008 + 8_192 == 51_388_416
    assert 48 * s["layer_params"] == 2_466_643_968
    # embedding and head 2 x 100,663,296; final norm and gate 4,097
    assert s["params"] == 2_466_643_968 + 2 * 100_663_296 + 4_097 \
        == 2_667_974_657
    assert (s["layers"], s["steps"]) == (48, 4)
    # 8 KiB a position a cache layer, 192 of them: 1.5 MiB
    assert s["kv_bytes_per_token"] == 192 * 8192 == 1_572_864
    assert s["step_bytes"] == 2 * (2_466_643_968 + 2048 + 2049)
    assert 4.93e9 < s["step_bytes"] < 4.94e9
    assert s["head_bytes"] == 2 * 100_663_296
    # the cell's fill: 2,900 positions held by 8 slots
    need = costs_ouro.tick_bytes(m, 2900, 8)
    assert need == 4 * s["step_bytes"] + s["head_bytes"] + 8 * 4096 \
        + 2900 * 1_572_864
    assert 24.4e9 < need < 24.6e9       # 29.9 ms at 819 GB/s
    # the loop is what costs: a model that ran its layers once would
    # read a quarter of the layers' bytes
    assert need - costs_ouro.tick_bytes(
        {**m, "total_ut_steps": 1}, 2900, 8) \
        == 3 * s["step_bytes"] + 2900 * 3 * 48 * 8192


def check_benchmark(bench: dict) -> None:
    """What this cell holds of BENCHMARK.json, each entry found by its
    name: a later PR appends configurations, cells and metrics."""
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "ouro-2.6b" and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert config["file"] == "benchmarks/configs/ouro-2.6b.json"
    assert config["reduced"] == ["max_position_embeddings"]
    assert {x["name"] for x in spec.reported_in(bench, "per_layer", CELL)} \
        == {"tick_host_ms", "batch_occupancy_pct", "kv_fill_pct",
            "decode_tick_ms", "prefill_p50_ms", "gap_p95_ms", "gap_p50_ms",
            "tick_hbm_roofline_pct", "device_idle_pct.serve",
            "tick_ms_per_layer_pass", "device_unfed_pct",
            "fetch_after_ready_pct", "gc_pause_ms_per_s"}
    assert {x["name"] for x in spec.reported_in(bench, "end_to_end", CELL)} \
        == {"out_tok_per_s", "gap_p99_ms", "setup_s"}
    assert next(x for x in bench["per_layer"]
                if x["name"] == "tick_ms_per_layer_pass") == {
        "name": "tick_ms_per_layer_pass", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serve model step",
        "moves": "out_tok_per_s", "workloads": [CELL]}


def test_the_configuration_is_the_catalogs_row():
    m = spec.cell(CELL)["model"]
    if CATALOG.exists():
        row = next(r for r in map(json.loads, CATALOG.open())
                   if r["name"] == "Ouro-2.6B")
        assert m["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if m.get(k) != v}
        assert changed == {"max_position_embeddings"}
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["source"] == m["source"]
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"], m["num_hidden_layers"], m["total_ut_steps"],
            m["early_exit_threshold"], m["rope_theta"], m["rms_norm_eps"],
            m["tie_word_embeddings"]) == \
        (2048, 16, 16, 128, 5632, 49152, 48, 4, 1, 1000000, 1e-6, False)
    assert m["max_position_embeddings"] == 768
    assert m["published"] == {"max_position_embeddings": 65536}
    assert {"published", "reduced_why", "assumed", "departures",
            "stands_for", "runs_through", "weights", "rehearse"} <= set(m)
    assert "nothing lies on another chip" in m["stands_for"]


def test_the_cell_as_issue_34_gives_it():
    check_benchmark(spec.benchmark())
    cell = spec.cell(CELL)
    assert cell["generator"] == "closed_loop"
    assert cell["adapter"] == "serve_ouro"
    assert cell["traffic"] == {
        "clients": 16, "grid": 512, "strata": 32,
        "prompt": {"median": 256, "sigma": 0.7, "min": 32, "max": 512},
        "output": {"median": 160, "sigma": 0.5, "min": 32, "max": 256}}
    # every other EngineConfig field at its default: no chunks, the
    # prefix cache on
    assert cell["engine"] == {"slots": 8, "max_len": 768, "block_size": 16}
    assert cell["check"]["shortest"] == 2 and cell["check"]["long_over"] == 512
    # three requests of 32 tokens or more are checked: never fewer rows
    assert cell["check"]["near_rows"] <= 3 * cell["traffic"]["output"]["min"]
    assert cell["trace_s"] == 3.0
    cfg = serve_ouro.model_config(cell["model"])
    assert (cfg.n_layers, cfg.total_ut_steps, cfg.cache_steps,
            cfg.n_heads, cfg.n_kv_heads, cfg.max_len) == \
        (48, 4, 4, 16, 16, 768)
    # three scans of 16 layers; a pool array of 64 segments x 385
    # blocks x 64 KiB stays under 2 GiB
    assert (cfg.pool_layers, cfg.cache_segments) == (16, 64)
    assert 64 * 385 * 65536 < 2 ** 31


def test_the_traffic_is_what_the_issue_says_of_it():
    """Eight requests of at most 512 + 256 fill the 6,144 positions;
    about half the prompts need the 512 bucket."""
    from benchmarks.traffic.lengths import Lengths

    lengths = Lengths(spec.cell(CELL)["traffic"], 3)
    pairs = [lengths.next()[1:] for _ in range(512)]
    prompts = np.asarray([p for p, _ in pairs])
    outputs = np.asarray([o for _, o in pairs])
    assert 245 < np.median(prompts) < 270 and 150 < np.median(outputs) < 170
    assert (prompts + outputs).max() <= 768
    assert 0.4 < (prompts > 256).mean() < 0.55
    assert prompts.min() >= 32 and outputs.min() >= 32


def test_the_window_opens_after_the_ramp_where_a_round_begins():
    """`AfterRamp`: the generator's rule, as many requests finished as
    there are slots, and the next request to be admitted the first of a
    round of 32; the generator itself is as it was."""
    from benchmarks.traffic import closed_loop

    cell = spec.cell(CELL)
    source = serve_ouro.AfterRamp(
        closed_loop.Source(cell["traffic"], 5, 49152),
        cell["traffic"]["strata"])
    sent = source.pop_due(0.0, 64)
    assert len(sent) == 16 and source.free == 0
    # every client has sent and every slot is filled: the generator
    # would open the window here
    assert source._source.window_may_open(1.0, 8, 8)
    opened = []
    for i in range(60):
        if source.window_may_open(2.0 + i, 8, 8):
            opened.append(source.finished)
        source.done(2.0 + i)
        assert len(source.pop_due(2.0 + i, 64)) == 1
    # request number finished + 8 is the next admitted: 32, 33, 34 and
    # 64, 65, 66 begin rounds (two that finish in one step pass the
    # first by one); 0-2 would, but the ramp is not over
    assert opened == [24, 25, 26, 56, 57, 58]
    source.finished = 24
    assert not source.window_may_open(99.0, 7, 8)    # a slot stands empty


def test_the_new_metric_reads_the_records_own_layer_passes():
    entry = next(x for x in spec.cell(CELL)["per_layer"]
                 if x["name"] == "tick_ms_per_layer_pass")
    assert entry["reader"] == "series_quantile"
    series = {"tick_device_per_layer_pass_s": [0.048 / 192, 0.0479 / 192,
                                               0.0495 / 192]}
    assert series_quantile.read({"series": series}, **entry["args"]) \
        == pytest.approx(0.25)
    # a program whose records count no layer pass: the series is empty
    # and the metric is left out of the line
    assert series_quantile.read(
        {"series": {"tick_device_per_layer_pass_s": []}},
        **entry["args"]) is None
    assert series_quantile.read({"series": {}}, **entry["args"]) is None


@pytest.fixture(scope="module")
def rehearsal_model():
    """The rehearsal's configuration and weights, and some finished
    requests served by the model's own greedy full forward."""
    from benchmarks.weights import decoder_weights
    from hyperion_tpu.models.ouro import Ouro

    cell = spec.cell(CELL)
    m = {**cell["model"], **cell["model"]["rehearse"]}
    check = {**cell["check"], **cell["rehearse"]["check"]}
    model = Ouro(serve_ouro.model_config(m))
    # default weights leave the products at 0.02 of their inputs: scale
    # so that the argmax is decided by more than rounding
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if path[-1].key in ("weight", "bias") else 14 * w,
        decoder_weights(model, 7))
    forward = jax.jit(lambda ids: model.apply({"params": params}, ids)[0])
    rng = np.random.default_rng(0)
    done = []
    for n, g in ((5, 16), (6, 16), (30, 30), (40, 20)):
        seq = rng.integers(1, m["vocab_size"], n).astype(np.int32)
        for _ in range(g):
            ids = np.zeros((1, 60), np.int32)
            ids[0, :len(seq)] = seq
            logits = forward(jnp.asarray(ids))
            seq = np.append(seq, np.int32(logits[0, len(seq) - 1].argmax()))
        done.append({"prompt": seq[:n], "tokens": seq[n:].tolist()})
    return m, check, model, params, done


def test_plain_reference_agrees_with_models_ouro(rehearsal_model):
    m, _, model, params, done = rehearsal_model
    ids = jnp.asarray(np.concatenate(
        [done[-1]["prompt"], done[-1]["tokens"]])[None])
    got, p = model.apply({"params": params}, ids)
    want, want_p, _ = reference.forward(params, ids, **reference.settings(m))
    assert float(jnp.abs(got - want).max()) < 1e-4 * max(
        1.0, float(want.std()))
    assert float(jnp.abs(p - want_p).max()) < 1e-4
    assert float(want.std()) > 0.1


def test_the_check_takes_the_shortest_and_one_long_request(rehearsal_model):
    m, check, _, params, done = rehearsal_model
    said = {}
    z, margin = serve_ouro.reference_slack(
        params, m, done, check, lambda **kw: said.update(kw))
    assert z.shape == margin.shape == (62,)
    assert not z.any() and (margin > 0).all()   # its own greedy tokens
    assert serve_ouro.agrees(z, margin, check["near_rows"])
    # fewer checked rows than the near-tie term may read: not correct
    assert not serve_ouro.agrees(z, margin, 63)
    assert said["reference_contexts"] == [21, 22, 60]       # 60 > 24
    # no finished request past `long_over`: the check cannot pass
    assert serve_ouro.reference_slack(
        params, m, done[:2], check, lambda **kw: None) is None


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_adapters_check(rehearsal_model, fault):
    """The served tokens against a reference wrong in one way, through
    the expression that decides `correct` (62 checked tokens here; what
    the chip reads at the cell's size is in PERF.md section 6)."""
    m, check, _, params, done = rehearsal_model
    with wrong_reference(fault):
        checked = serve_ouro.reference_slack(
            params, m, done, check, lambda **kw: None)
    assert not serve_ouro.agrees(*checked, check["near_rows"])
    # and the reference is itself again
    assert serve_ouro.agrees(*serve_ouro.reference_slack(
        params, m, done, check, lambda **kw: None), check["near_rows"])


def test_one_token_of_another_slot_moves_one_row(rehearsal_model):
    """The fault on the served side: one of the 62 checked tokens, the
    shortest request's middle one, is the token another request was
    served at that step; no other row moves."""
    m, check, _, params, done = rehearsal_model
    with another_slots_token():
        z, margin = serve_ouro.reference_slack(
            params, m, done, check, lambda **kw: None)
    assert np.flatnonzero(z).tolist() == [8] and z[8] > 0.3
    assert not serve_ouro.reference_slack(
        params, m, done, check, lambda **kw: None)[0].any()


@pytest.mark.parametrize("case, z, margin, ok", [
    # what the chip reads of the system: the served token is the
    # reference's best in half of the rows and a fifth of a std under
    # it elsewhere (bf16 at 192 layer passes)
    ("the system", [0.0] * 200 + [0.25] * 200, [0.3] * 250 + [0.05] * 150,
     True),
    ("the system's largest means", [0.0] * 100 + [0.26] * 300,
     [0.05] * 100 + [0.3] * 100 + [0.05] * 200, True),
    ("fewer rows than the near-tie term may read", [0.0] * 47, [0.05] * 47,
     False),
    # the mean over all rows, either side of 0.48
    ("under the mean's limit", [0.0] * 60 + [0.53] * 340,
     [0.05] * 60 + [0.5] * 340, True),
    ("over the mean's limit", [0.0] * 60 + [0.57] * 340,
     [0.05] * 60 + [0.5] * 340, False),
    # the near-tie rows, either side of 0.55: the wide rows are right
    ("under the near-tie limit", [0.54] * 100 + [0.0] * 300,
     [0.05] * 100 + [0.5] * 300, True),
    ("over the near-tie limit", [0.56] * 100 + [0.0] * 300,
     [0.05] * 100 + [0.5] * 300, False),
    # fewer than 48 rows within `NEAR`: the 48 of the smallest margin
    # are read, and the flipped ones among them count
    ("few near-ties, the next rows flipped", [0.0] * 360 + [0.7] * 40,
     [0.5] * 352 + [0.08] * 8 + [0.2] * 40, False),
    # one token in four hundred, either side of 1.9: both means read
    # 0.005
    ("the system's worst token", [0.0] * 399 + [1.38], [0.3] * 400, True),
    ("under the worst token's limit", [0.0] * 399 + [1.89], [0.3] * 400,
     True),
    ("another slot's token", [0.0] * 399 + [2.7], [0.3] * 400, False),
    # the faults' smallest readings on the chip (PERF.md section 6)
    ("a cache shared between the steps", [0.96] * 400, [0.3] * 400, False),
    ("fp8 weights", [1.67] * 400, [0.3] * 400, False),
])
def test_what_the_three_limits_separate(case, z, margin, ok):
    assert serve_ouro.agrees(
        np.asarray(z), np.asarray(margin), 48) is ok, case
    assert (serve_ouro.NEAR_MEAN_SLACK, serve_ouro.MEAN_SLACK,
            serve_ouro.WORST_SLACK) == (0.55, 0.48, 1.9)

