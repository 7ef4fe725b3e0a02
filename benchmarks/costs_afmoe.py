"""What a decode tick of the served `afmoe` configuration has to read,
from shapes alone: the numerator of `tick_hbm_roofline_pct` in the
cells that run `models/afmoe.py`. The routed products are XLA's own
grouped matrix product (`ragged_dot`), no Pallas kernel: the tick's
share of the HBM roofline is the bound, and no kernel has a row of its
own here. Kept with the benchmark so that a PR that changes the program
cannot change it."""

from __future__ import annotations


def afmoe_sizes(m: dict, bytes_per_value: int = 2) -> dict:
    """`m` is the configuration file (HF key names; `num_experts` is
    the experts HELD here, `router_experts` the router's width)."""
    d, h = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    kinds = [m["layer_types"][i] for i in m["layers_kept"]]
    n_dense = m["num_dense_layers"]
    n_moe = len(kinds) - n_dense
    attn = 3 * d * nq * h + 2 * d * nkv * h             # q, gate, o; k, v
    norms = 4 * d + 2 * h
    expert = 3 * d * m["moe_intermediate_size"]
    shared = expert * m["num_shared_experts"]
    router = d * m["router_experts"] + m["router_experts"]
    dense_mlp = 3 * d * m["intermediate_size"]
    head = d * m["vocab_size"]
    outside = (len(kinds) * (attn + norms) + n_dense * dense_mlp
               + n_moe * (shared + router) + head + d)
    return {
        "expert_params": expert,
        "expert_layers": n_moe,
        "experts_held": m["num_experts"],
        "params": outside + head + n_moe * m["num_experts"] * expert,
        # a decode tick reads everything outside the routed experts and
        # the output head once; of the embedding one row per slot
        "tick_fixed_bytes": outside * bytes_per_value,
        "expert_bytes": expert * bytes_per_value,
        # keys and values of one position, over the layers of each kind
        "kv_bytes_per_token": {
            kind: 2 * nkv * h * bytes_per_value * sum(
                t == name for t in kinds)
            for kind, name in (("full", "full_attention"),
                               ("window", "sliding_attention"))},
    }


def tick_bytes(m: dict, kv_tokens: dict, experts_touched: float) -> float:
    """Least bytes one decode tick reads from HBM: the weights outside
    the routed experts once, each held expert that the tick's own
    routing touched once (`experts_touched`, summed over the expert
    layers), and the keys and values each layer kind must read:
    `kv_tokens[kind]` positions, on a windowed kind the positions it
    still holds (a window, and up to two blocks' slack: 0.4 %)."""
    s = afmoe_sizes(m)
    return (s["tick_fixed_bytes"] + experts_touched * s["expert_bytes"]
            + sum(n * s["kv_bytes_per_token"][kind]
                  for kind, n in kv_tokens.items()))
