"""What a decode tick of the served SmallThinker configuration has to
read, from shapes alone: the numerator of `tick_hbm_roofline_pct` in
the cell that runs `models/smallthinker.py`. The routed products are
XLA's own grouped matrix product (`ragged_dot`), no Pallas kernel of
this model's: the tick's share of the HBM roofline is the bound, and no
kernel has a row of its own here. Kept with the benchmark so that a PR
that changes the program cannot change it."""

from __future__ import annotations


def smallthinker_sizes(m: dict, bytes_per_value: int = 2) -> dict:
    """`m` is the configuration file (HF key names); every expert of a
    layer is held where the layer is."""
    d, h = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    sliding = [m["sliding_window_layout"][i] for i in m["layers_kept"]]
    layers = len(sliding)
    experts = m["moe_num_primary_experts"]
    attn = 2 * d * nq * h + 2 * d * nkv * h             # q, o; k, v
    norms = 2 * d
    expert = 3 * d * m["moe_ffn_hidden_size"]
    router = d * experts
    head = d * m["vocab_size"]
    outside = layers * (attn + norms + router) + head + d
    return {
        "expert_params": expert,
        "expert_layers": layers,        # every layer is an expert layer
        "experts": experts,
        "params": outside + head + layers * experts * expert,
        # a decode tick reads everything outside the routed experts and
        # the output head once; of the embedding one row per slot
        "tick_fixed_bytes": outside * bytes_per_value,
        "expert_bytes": expert * bytes_per_value,
        # keys and values of one position, over the layers of each kind
        "kv_bytes_per_token": {
            "full": 2 * nkv * h * bytes_per_value * sliding.count(0),
            "window": 2 * nkv * h * bytes_per_value * sliding.count(1)},
    }


def tick_bytes(m: dict, kv_tokens: dict, experts_touched: float) -> float:
    """Least bytes one decode tick reads from HBM: the weights outside
    the routed experts once, each expert that the tick's own routing
    touched once (`experts_touched`, summed over the layers), and the
    keys and values each layer kind must read: `kv_tokens[kind]`
    positions, on the windowed kind the positions it still holds (a
    window, and up to two blocks' slack)."""
    s = smallthinker_sizes(m)
    return (s["tick_fixed_bytes"] + experts_touched * s["expert_bytes"]
            + sum(n * s["kv_bytes_per_token"][kind]
                  for kind, n in kv_tokens.items()))
