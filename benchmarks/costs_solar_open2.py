"""What a decode tick of the served `solar_open2` configuration has to
move, from shapes alone: the numerator of `tick_hbm_roofline_pct` and of
`state_bytes_pct` in the cell that runs `models/solar_open2.py`. The
recurrent layers' state update has no kernel of its own yet (XLA's
fusions in the tick), so the tick's share of the HBM roofline is the
bound. Kept with the benchmark so that a PR that changes the program
cannot change it."""

from __future__ import annotations


def solar_sizes(m: dict, bytes_per_value: int = 2) -> dict:
    """`m` is the configuration file (HF key names; `n_routed_experts`
    is the experts HELD here, `router_experts` the router's width)."""
    d, h = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    la = m["linear_attn_config"]
    H, K, taps = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    rank = K        # kda_use_full_proj false: the f and g projections' rank
    kinds = ["full" if i in m["gqa_layers"] else "recurrent"
             for i in m["layers_kept"]]
    n_gqa, n_kda = kinds.count("full"), kinds.count("recurrent")
    kda = (3 * d * H * K + H * K * d             # q, k, v; o
           + 2 * (d * rank + rank * H * K)       # f, g: low rank
           + d * H                               # b
           + taps * 3 * H * K + H + H * K + K)   # conv, A_log, dt_bias, o_norm
    gqa = 3 * d * nq * h + 2 * d * nkv * h       # q, gate, o; k, v
    expert = 3 * d * m["moe_intermediate_size"]
    shared = expert * m["n_shared_experts"]
    router = d * m["router_experts"] + m["router_experts"]
    head = d * m["vocab_size"]
    norms = 2 * d * len(kinds) + d
    outside = (n_kda * kda + n_gqa * gqa + len(kinds) * (shared + router)
               + head + norms)
    return {
        "kda_params": kda, "gqa_params": gqa,
        "expert_params": expert,
        "expert_layers": len(kinds),
        "experts_held": m["n_routed_experts"],
        "recurrent_layers": n_kda,
        "params": outside + head
        + len(kinds) * m["n_routed_experts"] * expert,
        # a decode tick reads everything outside the routed experts and
        # the embedding once; of the embedding one row per slot (A_log,
        # dt_bias, the norms and the router's bias are float32, counted
        # at the served width: 0.01 % of these bytes)
        "tick_fixed_bytes": outside * bytes_per_value,
        "expert_bytes": expert * bytes_per_value,
        # keys and values of one position, over the GQA layers
        "kv_bytes_per_token": 2 * nkv * h * bytes_per_value * n_gqa,
        # one slot's state in one recurrent layer: S in float32, the
        # conv's last taps - 1 inputs in the served type
        "state_row_bytes": H * K * K * 4
        + (taps - 1) * 3 * H * K * bytes_per_value,
    }


def state_bytes(m: dict, recurrent_rows: float) -> float:
    """Least bytes a tick moves of the recurrent state: each advanced
    row's S and conv tail read once and written once (`recurrent_rows`:
    rows x recurrent layers, the tick record's counter)."""
    return 2 * recurrent_rows * solar_sizes(m)["state_row_bytes"]


def tick_bytes(m: dict, kv_tokens: float, experts_touched: float,
               recurrent_rows: float) -> float:
    """Least bytes one decode tick moves through HBM: the weights outside
    the routed experts once, each held expert that the tick's own routing
    touched once (`experts_touched`, summed over the expert layers), the
    keys and values of the `kv_tokens` positions the live slots hold in
    the GQA layers' pool, and the state of the rows the tick advanced,
    read and written (`state_bytes`)."""
    s = solar_sizes(m)
    return (s["tick_fixed_bytes"] + experts_touched * s["expert_bytes"]
            + kv_tokens * s["kv_bytes_per_token"]
            + state_bytes(m, recurrent_rows))
