"""The cell `smallthinker.chatmix-saturated` and what PR 31 brought with
it: `adapters/serve_smallthinker.py`, `costs_smallthinker.py`,
`reference/smallthinker.py`, the metric `expert_tokens_per_touched`.
The cell's rehearsal itself is a case of `test_bench_harness.py`'s
parametrised test."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from smallthinker_faults import (  # noqa: E402
    FAULTS, another_slots_token, fault as wrong_reference)
from benchmarks import costs_smallthinker, spec  # noqa: E402
from benchmarks.adapters import serve_smallthinker  # noqa: E402
from benchmarks.readers import counter_ratio  # noqa: E402
from benchmarks.reference import smallthinker as reference  # noqa: E402

CELL = "smallthinker.chatmix-saturated"


def test_costs_from_shapes():
    """ISSUE 31's hand arithmetic."""
    m = spec.cell(CELL)["model"]
    s = costs_smallthinker.smallthinker_sizes(m)
    assert s["expert_params"] == 3 * 2560 * 768
    assert s["expert_bytes"] == 11_796_480              # 11.8 MB
    assert (s["expert_layers"], s["experts"]) == (8, 64)
    # attention 20,971,520 + router 163,840 + norms 5,120 + experts
    # 377,487,360 a layer, eight of them, embedding and head
    # 777,912,320, the final norm: 3.967 B parameters
    layer = 20_971_520 + 163_840 + 5_120 + 377_487_360
    assert layer == 398_627_840
    assert s["params"] == 8 * layer + 777_912_320 + 2560 == 3_966_937_600
    assert 2 * s["params"] == 7_933_875_200             # 7.39 GiB
    # 2 KiB a position a layer: two full layers, six windowed ones
    assert s["kv_bytes_per_token"] == {"full": 2 * 2048, "window": 6 * 2048}
    # everything outside the experts, once: the head and eight layers'
    # attention, routers and norms
    assert s["tick_fixed_bytes"] == 2 * (
        8 * (20_971_520 + 163_840 + 5_120) + 151936 * 2560 + 2560)
    assert 1.10e9 < s["tick_fixed_bytes"] < 1.13e9
    need = costs_smallthinker.tick_bytes(m, {"full": 1000, "window": 600}, 40)
    assert need == s["tick_fixed_bytes"] + 40 * 11_796_480 \
        + 1000 * 4096 + 600 * 12288


def check_benchmark(bench: dict) -> None:
    """What this cell holds of BENCHMARK.json, each entry found by its
    name: a later PR appends configurations, cells and metrics."""
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["config"] == "smallthinker-21b-l8" and entry["chips"] == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "smallthinker-21b-l8")
    assert config["file"] == "benchmarks/configs/smallthinker-21b-l8.json"
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert {x["name"] for x in spec.reported_in(bench, "per_layer", CELL)} \
        == {"tick_host_ms", "batch_occupancy_pct", "kv_fill_pct",
            "decode_tick_ms", "prefill_p50_ms", "gap_p95_ms", "gap_p50_ms",
            "tick_hbm_roofline_pct", "device_idle_pct.serve",
            "expert_picks_per_tick", "experts_touched_pct",
            "kv_window_released_pct", "expert_tokens_per_touched",
            "device_unfed_pct", "fetch_after_ready_pct",
            "gc_pause_ms_per_s"}
    assert {x["name"] for x in spec.reported_in(bench, "end_to_end", CELL)} \
        == {"out_tok_per_s", "gap_p99_ms", "setup_s"}


def test_the_cell_as_issue_31_gives_it():
    check_benchmark(spec.benchmark())
    cell = spec.cell(CELL)
    m = cell["model"]
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_ffn_hidden_size"], m["moe_num_primary_experts"],
            m["moe_num_active_primary_experts"], m["sliding_window_size"],
            m["vocab_size"], m["rope_theta"], m["rms_norm_eps"]) == \
        (2560, 28, 4, 128, 768, 64, 6, 4096, 151936, 1500000, 1e-6)
    assert (m["num_hidden_layers"], m["max_position_embeddings"]) == \
        (8, 8192)
    assert m["published"] == {"num_hidden_layers": 52,
                              "max_position_embeddings": 16384}
    assert m["layers_kept"] == list(range(8))
    assert m["sliding_window_layout"] == m["rope_layout"] == [0, 1, 1, 1] * 13
    assert {"published", "reduced_why", "assumed", "departures",
            "stands_for", "runs_through", "weights", "layers_kept",
            "rehearse"} <= set(m)
    assert cell["generator"] == "closed_loop"
    assert cell["adapter"] == "serve_smallthinker"
    assert cell["traffic"] == {
        "clients": 96, "grid": 512, "strata": 32,
        "prompt": {"median": 1024, "sigma": 1.0, "min": 32, "max": 7168},
        "output": {"median": 512, "sigma": 0.6, "min": 64, "max": 1024}}
    assert cell["engine"] == {"slots": 48, "max_len": 8192,
                              "block_size": 16, "prefill_chunk": 512,
                              "prefix_cache": False}
    # ISSUE 31's three, and the fewest rows the near-tie term may read
    assert cell["check"] == {"shortest": 2, "long_over": 4608,
                             "pad_to": 2048, "near_rows": 100}
    assert cell["trace_s"] == 3.0
    cfg = serve_smallthinker.model_config(m)
    assert cfg.layer_kinds == (
        ("full", 0), ("window", 4096), ("window", 4096),
        ("window", 4096)) * 2
    assert cfg.rope_layout == (0, 1, 1, 1) * 2
    assert (cfg.n_experts, cfg.top_k) == (64, 6)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.max_len) == (28, 4, 8192)


def test_the_traffic_is_what_the_issue_says_of_it():
    """One request in nine passes the window; few prompts sit at the
    upper clip."""
    from benchmarks.traffic.lengths import Lengths

    lengths = Lengths(spec.cell(CELL)["traffic"], 3)
    pairs = [lengths.next()[1:] for _ in range(512)]
    prompts = np.asarray([p for p, _ in pairs])
    total = np.asarray([p + o for p, o in pairs])
    assert 900 < np.median(prompts) < 1150
    assert 0.08 < (total > 4096).mean() < 0.14
    assert (prompts == 7168).mean() < 0.04
    assert total.max() <= 8192


TICKS = [
    {"kv_tokens": 1000, "kv_tokens_window": 400, "expert_picks_held": 288,
     "experts_touched": 60, "expert_load_max": 9},
    {"kv_tokens": 3000, "kv_tokens_window": 600, "expert_picks_held": 144,
     "experts_touched": 48, "expert_load_max": 7},
]


@pytest.mark.parametrize("metric, want", [
    ("expert_tokens_per_touched", 432 / 108),
    ("expert_picks_per_tick", 432 / 16),
    ("experts_touched_pct", 100 * 108 / 1024),
])
def test_expert_readers_on_made_up_tick_records(metric, want):
    entry = next(x for x in spec.cell(CELL)["per_layer"]
                 if x["name"] == metric)
    assert entry["reader"] == "counter_ratio"
    ctx = {"counted": [{**c, "expert_layers": 8,
                        "experts_held_all_layers": 512} for c in TICKS]}
    assert counter_ratio.read(ctx, **entry["args"]) == pytest.approx(want)
    # a program that counts none of it (the parent): the metric is left out
    assert counter_ratio.read(
        {"counted": [{"kv_tokens": 5}]}, **entry["args"]) is None
    assert counter_ratio.read({}, **entry["args"]) is None


@pytest.fixture(scope="module")
def rehearsal_model():
    """The rehearsal's configuration and weights, and some finished
    requests served by the model's own greedy full forward."""
    from benchmarks.weights import decoder_weights
    from hyperion_tpu.models.smallthinker import Smallthinker

    cell = spec.cell(CELL)
    m = {**cell["model"], **cell["model"]["rehearse"]}
    check = {**cell["check"], **cell["rehearse"]["check"]}
    model = Smallthinker(serve_smallthinker.model_config(m))
    # default weights leave logits of std 0.02: scale so routing and
    # the argmax are decided by more than rounding
    params = jax.tree.map(lambda w: 14 * w if w.ndim > 1 else w,
                          decoder_weights(model, 7))
    # one compiled forward at the longest context: the model is causal,
    # so the padding behind a sequence changes nothing before it
    forward = jax.jit(lambda ids: model.apply({"params": params}, ids))
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


def test_plain_reference_agrees_with_models_smallthinker(rehearsal_model):
    m, _, model, params, done = rehearsal_model
    ids = jnp.asarray(np.concatenate(
        [done[-1]["prompt"], done[-1]["tokens"]])[None])
    got = model.apply({"params": params}, ids)
    want = reference.logits(params, ids, **reference.settings(m))
    assert float(jnp.abs(got - want).max()) < 1e-4 * max(
        1.0, float(want.std()))
    assert float(want.std()) > 0.1


def test_the_check_takes_the_shortest_and_one_long_request(rehearsal_model):
    m, check, _, params, done = rehearsal_model
    said = {}
    z, margin = serve_smallthinker.reference_slack(
        params, m, done, check, lambda **kw: said.update(kw))
    assert z.shape == margin.shape == (62,)
    assert not z.any() and (margin > 0).all()   # its own greedy tokens
    assert serve_smallthinker.agrees(z, margin, check["near_rows"])
    # fewer checked rows than the near-tie term may read: not correct
    assert not serve_smallthinker.agrees(z, margin, 63)
    assert said["reference_contexts"] == [21, 22, 60]       # 60 > 24
    # no finished request past `long_over`: the check cannot pass
    assert serve_smallthinker.reference_slack(
        params, m, done[:2], check, lambda **kw: None) is None


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_adapters_check(rehearsal_model, fault):
    """The served tokens against a reference wrong in one way, through
    the expression that decides `correct` (62 checked tokens here; what
    the chip reads at the cell's size is in PERF.md section 6)."""
    m, check, _, params, done = rehearsal_model
    with wrong_reference(fault):
        checked = serve_smallthinker.reference_slack(
            params, m, done, check, lambda **kw: None)
    assert not serve_smallthinker.agrees(*checked, check["near_rows"])
    # and the reference is itself again
    assert serve_smallthinker.agrees(*serve_smallthinker.reference_slack(
        params, m, done, check, lambda **kw: None), check["near_rows"])


def test_one_token_of_another_slot_moves_one_row(rehearsal_model):
    """The fault on the served side: one of the 62 checked tokens, the
    shortest request's middle one, is the token another request was
    served at that step; no other row moves. How far under its row's
    best such a token lies at 151936 ids, against `WORST_SLACK`, is the
    chip's reading (PERF.md section 6); among this model's 96 ids the
    best logit stands only 2.5 std over the mean."""
    m, check, _, params, done = rehearsal_model
    with another_slots_token():
        z, margin = serve_smallthinker.reference_slack(
            params, m, done, check, lambda **kw: None)
    assert np.flatnonzero(z).tolist() == [8] and z[8] > 0.3
    assert not serve_smallthinker.reference_slack(
        params, m, done, check, lambda **kw: None)[0].any()


@pytest.mark.parametrize("case, z, margin, ok", [
    # 1000 rows, 300 of them near-ties of which 20 flipped by their width
    ("the system", [0.0] * 980 + [0.02] * 20,
     [0.3] * 700 + [0.05] * 280 + [0.02] * 20, True),
    # a run locked in repetitions: under 100 rows within `NEAR`, so the
    # 100 of the smallest margin are read; none flipped
    ("few near-ties, none flipped", [0.0] * 1000,
     [0.4] * 990 + [0.08] * 10, True),
    # ... and read they are: 40 rows of margin 0.2 flipped by 0.02 put
    # the mean over those 100 at 0.008, over all rows at 0.0008
    ("few near-ties, the next rows flipped", [0.0] * 960 + [0.02] * 40,
     [0.4] * 900 + [0.25] * 50 + [0.08] * 10 + [0.2] * 40, False),
    ("fewer rows than the near-tie term may read", [0.0] * 99,
     [0.05] * 99, False),
    # every logit moved a little: a third of the near-ties flipped, the
    # mean over ALL rows reads 0.002 as the system's can
    ("rounded weights, diluted", [0.0] * 960 + [0.05] * 40,
     [0.4] * 880 + [0.08] * 80 + [0.05] * 40, False),
    # logits moved by more than a near-tie's width: off on wide rows
    ("a gross fault", [0.0] * 900 + [0.25] * 100,
     [0.5] * 900 + [0.25] * 100, False),
    # one token in a thousand, 1 std under its row's best: both means
    # read 0.001 (what a limit of 2.0 on the worst token let pass)
    ("one wrong token", [0.0] * 999 + [1.0], [0.3] * 1000, False),
    ("the system's worst token", [0.0] * 999 + [0.22], [0.3] * 1000, True),
])
def test_what_the_three_limits_separate(case, z, margin, ok):
    assert serve_smallthinker.agrees(
        np.asarray(z), np.asarray(margin), 100) is ok, case
