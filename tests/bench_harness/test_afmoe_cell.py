"""The cell `trinity.longmix-saturated` and what PR 26 brought with it:
`adapters/serve_afmoe.py`, `costs_afmoe.py`, `reference/afmoe.py`,
`readers/counter_ratio.py`. The cell's rehearsal itself is a case of
`test_bench_harness.py`'s parametrised test."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from afmoe_faults import FAULTS, fault as wrong_reference  # noqa: E402
from benchmarks import costs_afmoe, spec  # noqa: E402
from benchmarks.adapters import serve_afmoe  # noqa: E402
from benchmarks.readers import counter_ratio  # noqa: E402
from benchmarks.reference import afmoe as reference  # noqa: E402

CELL = "trinity.longmix-saturated"


def test_costs_from_shapes():
    m = spec.cell(CELL)["model"]
    s = costs_afmoe.afmoe_sizes(m)
    assert s["expert_params"] == 3 * 3072 * 3072
    assert s["expert_bytes"] == 56_623_104              # 56.6 MB
    assert (s["expert_layers"], s["experts_held"]) == (4, 32)
    assert abs(s["params"] - 4.32e9) < 1e7              # ISSUE 26's cut
    # 4 KiB a position a layer: one full layer, four windowed ones
    assert s["kv_bytes_per_token"] == {"full": 4096, "window": 4 * 4096}
    # everything outside the routed experts, once: about 1.2 GB
    assert 1.15e9 < s["tick_fixed_bytes"] < 1.30e9
    need = costs_afmoe.tick_bytes(m, {"full": 1000, "window": 600}, 40)
    assert need == s["tick_fixed_bytes"] + 40 * 56_623_104 \
        + 1000 * 4096 + 600 * 16384


def test_the_cell_as_issue_26_gives_it():
    cell = spec.cell(CELL)
    m = cell["model"]
    assert (m["hidden_size"], m["head_dim"], m["moe_intermediate_size"],
            m["intermediate_size"], m["sliding_window"],
            m["num_experts_per_tok"]) == (3072, 128, 3072, 12288, 4096, 4)
    assert [m["layer_types"][i] for i in m["layers_kept"]] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    assert (m["router_experts"], m["num_experts"], m["experts_held"]) == \
        (256, 32, [0, 32])
    assert {"published", "reduced_why", "assumed", "stands_for",
            "layers_kept", "weights", "rehearse"} <= set(m)
    assert cell["generator"] == "closed_loop"
    assert cell["traffic"] == {
        "clients": 48, "grid": 512, "strata": 32,
        "prompt": {"median": 6144, "sigma": 0.7, "min": 256, "max": 11264},
        "output": {"median": 320, "sigma": 0.6, "min": 32, "max": 1024}}
    assert cell["engine"] == {"slots": 24, "max_len": 12288,
                              "block_size": 16, "prefill_chunk": 512,
                              "prefix_cache": False}
    cfg = serve_afmoe.model_config(m)
    assert cfg.layer_kinds == (("window", 4096),) * 4 + (("full", 0),)
    assert cfg.n_dense_layers == 1 and cfg.experts_held == (0, 32)
    assert {x["name"] for x in cell["per_layer"]} >= {
        "expert_picks_per_tick", "experts_touched_pct",
        "kv_window_released_pct", "tick_hbm_roofline_pct",
        "prefill_p50_ms", "gap_p95_ms"}
    assert {x["name"] for x in cell["end_to_end"]} == {
        "out_tok_per_s", "gap_p99_ms", "setup_s"}


TICKS = [
    {"kv_tokens": 1000, "kv_tokens_window": 400, "expert_picks_held": 12,
     "experts_touched": 9, "expert_load_max": 3},
    {"kv_tokens": 3000, "kv_tokens_window": 600, "expert_picks_held": 20,
     "experts_touched": 15, "expert_load_max": 4},
]


def ctx(ticks):
    return {"counted": [{**c, "expert_layers": 4,
                         "experts_held_all_layers": 128} for c in ticks]}


@pytest.mark.parametrize("metric, want", [
    ("expert_picks_per_tick", 32 / 8),
    ("experts_touched_pct", 100 * 24 / 256),
    ("kv_window_released_pct", 100 * (1 - 1000 / 4000)),
])
def test_counter_readers_on_made_up_tick_records(metric, want):
    entry = next(x for x in spec.cell(CELL)["per_layer"]
                 if x["name"] == metric)
    assert entry["reader"] == "counter_ratio"
    assert counter_ratio.read(ctx(TICKS), **entry["args"]) == \
        pytest.approx(want)
    # a program that counts none of it: the metric is left out
    assert counter_ratio.read(
        ctx([{"kv_tokens": 5}]), **entry["args"]) is None
    assert counter_ratio.read({}, **entry["args"]) is None


@pytest.fixture(scope="module")
def rehearsal_model():
    """The rehearsal's configuration and weights, and some finished
    requests served by the model's own greedy full forward."""
    from hyperion_tpu.models.afmoe import Afmoe

    cell = spec.cell(CELL)
    m = {**cell["model"], **cell["model"]["rehearse"]}
    check = {**cell["check"], **cell["rehearse"]["check"]}
    model = Afmoe(serve_afmoe.model_config(m))
    params = serve_afmoe.afmoe_weights(model, 7)
    # default weights leave logits of std 0.02: scale so routing and
    # the argmax are decided by more than rounding
    params = jax.tree.map(lambda w: 12 * w if w.ndim > 1 else w, params)
    rng = np.random.default_rng(0)
    done = []
    for n, g in ((5, 16), (6, 16), (30, 30), (40, 20)):
        seq = rng.integers(1, m["vocab_size"], n).astype(np.int32)
        for _ in range(g):
            logits = model.apply({"params": params}, jnp.asarray(seq[None]))
            seq = np.append(seq, np.int32(logits[0, -1].argmax()))
        done.append({"prompt": seq[:n], "tokens": seq[n:].tolist()})
    return m, check, model, params, done


def test_plain_reference_agrees_with_models_afmoe(rehearsal_model):
    m, _, model, params, done = rehearsal_model
    assert params["layer_1"]["moe"]["expert_bias"].dtype == jnp.float32
    assert not params["layer_1"]["moe"]["expert_bias"].any()
    ids = jnp.asarray(np.concatenate(
        [done[-1]["prompt"], done[-1]["tokens"]])[None])
    got = model.apply({"params": params}, ids)
    want = reference.logits(params, ids, **reference.settings(m))
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(want.std())


def test_the_check_takes_the_shortest_and_one_long_request(rehearsal_model):
    m, check, _, params, done = rehearsal_model
    picked = serve_afmoe.checked_requests(done, check)
    assert [len(r["prompt"]) for r in picked] == [5, 6, 30]   # 60 > 24
    said = {}
    z = serve_afmoe.reference_slack(
        params, m, done, check, lambda **kw: said.update(kw))
    assert z.shape == (62,) and not z.any()   # its own greedy tokens
    assert serve_afmoe.agrees(z)
    assert said["reference_contexts"] == [21, 22, 60]
    # no finished request past `long_over`: the check cannot pass
    assert serve_afmoe.reference_slack(
        params, m, done[:2], check, lambda **kw: None) is None


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_adapters_check(rehearsal_model, fault):
    """The served tokens against a reference wrong in one way, through
    the expression that decides `correct` (62 checked tokens here; what
    the chip reads at the cell's size is in PERF.md section 6)."""
    m, check, _, params, done = rehearsal_model
    with wrong_reference(fault):
        z = serve_afmoe.reference_slack(
            params, m, done, check, lambda **kw: None)
    assert not serve_afmoe.agrees(z)
    # and the reference is itself again
    assert serve_afmoe.agrees(serve_afmoe.reference_slack(
        params, m, done, check, lambda **kw: None))


@pytest.mark.parametrize("case, z, ok", [
    # 800 tokens, 4 % of them not the reference's best by a little
    ("the system", [0.0] * 768 + [0.08] * 28 + [0.5] * 3 + [0.9], True),
    # two wrong tokens among hundreds: the mean reads 0.010
    ("two wrong tokens", [0.0] * 798 + [4.0] * 2, False),
    ("a tail: 3 % of the tokens 0.35 below", [0.0] * 776 + [0.35] * 24, False),
    ("every logit moved a little", [0.0] * 500 + [0.05] * 300, False),
])
def test_what_the_three_limits_separate(case, z, ok):
    assert serve_afmoe.agrees(np.asarray(z)) is ok, case
