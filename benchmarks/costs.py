"""What a step has to move or compute, from shapes alone: the
numerators of the roofline shares. Kept with the benchmark so that a PR
that changes the program cannot change them."""

from __future__ import annotations


def decoder_sizes(m: dict, bytes_per_value: int = 2) -> dict:
    """`m` is the configuration file (HF key names)."""
    d, h = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    layer = (d * nq * h + 2 * d * nkv * h + nq * h * d      # q, k, v, o
             + 3 * d * m["intermediate_size"])              # gate, up, down
    head = d * m["vocab_size"]
    return {
        "layer_params": layer,
        "params": m["num_hidden_layers"] * layer + 2 * head,
        # a decode tick reads every layer and the output head once; of
        # the embedding table it reads one row per slot
        "tick_weight_bytes":
            (m["num_hidden_layers"] * layer + head) * bytes_per_value,
        "kv_bytes_per_token":
            2 * m["num_hidden_layers"] * nkv * h * bytes_per_value,
    }


def tick_bytes(m: dict, kv_tokens: float) -> float:
    """Least bytes one decode tick reads from HBM: the weights once and
    the keys and values of every token the live slots hold."""
    s = decoder_sizes(m)
    return s["tick_weight_bytes"] + kv_tokens * s["kv_bytes_per_token"]


def gpt_train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per matmul parameter, and
    causal attention's two products at half the square (a position
    needs the keys up to itself; the masked half is not required)."""
    d, L = m["n_embd"], m["n_layer"]
    matmul_params = L * 12 * d * d + d * m["vocab_size"]
    attn_fwd = L * 2 * 2 * seq * d / 2
    return 6 * matmul_params + 3 * attn_fwd
