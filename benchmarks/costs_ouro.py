"""What a decode tick of the served Ouro configuration has to read,
from shapes alone: the numerator of `tick_hbm_roofline_pct` in the cell
that runs `models/ouro.py`. The model applies its layers
`total_ut_steps` times a token over the same weights, and no schedule
reads them less often: the chip's 128 MiB of VMEM hold none of a
step's 4.93 GB until the next step comes round to them, and step `t +
1` of the first layer waits for step `t` of the last. So the layers
count once a step, and a reading over 105 % would mean the program
skipped a pass. No Pallas kernel is this model's own (the tick reads
the pools through `ops/pallas/paged_attention.py`, as every served
model's does): the tick's share of the HBM roofline is the bound, and
no kernel has a row of its own here. Kept with the benchmark so that a
PR that changes the program cannot change it."""

from __future__ import annotations


def ouro_sizes(m: dict, bytes_per_value: int = 2) -> dict:
    """`m` is the configuration file (HF key names)."""
    d, h = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    layers, steps = m["num_hidden_layers"], m["total_ut_steps"]
    attn = 2 * d * nq * h + 2 * d * nkv * h             # q, o; k, v
    mlp = 3 * d * m["intermediate_size"]                # gate, up, down
    layer = attn + mlp + 4 * d                          # four norms
    head = d * m["vocab_size"]
    return {
        "layer_params": layer,
        "layers": layers,
        "steps": steps,
        # the layers, embedding and head, the final norm, the exit gate
        # with its bias
        "params": layers * layer + 2 * head + d + d + 1,
        # one pass over the layers, with the final norm and the gate
        # that end it
        "step_bytes": (layers * layer + 2 * d + 1) * bytes_per_value,
        "head_bytes": head * bytes_per_value,
        "embed_row_bytes": d * bytes_per_value,
        # keys and values of one position: a cache layer a step and
        # weight layer
        "kv_bytes_per_token":
            2 * steps * layers * nkv * h * bytes_per_value,
    }


def tick_bytes(m: dict, kv_tokens: float, slots: int) -> float:
    """Least bytes one decode tick reads from HBM: the layers once a
    step of the loop, the output head once, one row of the embedding a
    slot, and the keys and values of every position the live slots
    hold, in every step's cache."""
    s = ouro_sizes(m)
    return (s["steps"] * s["step_bytes"] + s["head_bytes"]
            + slots * s["embed_row_bytes"]
            + kv_tokens * s["kv_bytes_per_token"])
