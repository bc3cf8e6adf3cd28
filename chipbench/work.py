"""Operations and bytes that the mathematics needs, from shapes alone.

Model FLOPs count a multiply-add as two, causal attention at the keys a query
really sees, the forward pass once and the backward pass twice; nothing that is
recomputed and no padding. ``cfg`` is a configuration's file as a dict.
"""

from __future__ import annotations


def mean_keys(seq_len: int, window=None) -> float:
    """Keys a query sees, averaged over the positions of a causal sequence."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    # positions below the window see position+1 keys, the others `window`
    return (window * (window + 1) / 2.0 + (seq_len - window) * window) / seq_len


# ------------------------------------------------------------------ llama family
def llama_layer_matmul_flops_per_token(cfg: dict) -> float:
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2.0 * (d * q + 2 * d * kv + q * d + 3 * d * i)


def llama_attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """QK^T and PV of one layer, forward, for one token of a causal sequence."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * 2.0 * q * mean_keys(seq_len, cfg.get("sliding_window"))


def llama_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (forward x 3) of the layers and the head, a token.
    The head runs on the ``seq_len - 1`` positions that have a label."""
    layers = cfg["num_hidden_layers"] * (
        llama_layer_matmul_flops_per_token(cfg) + llama_attention_flops_per_token(cfg, seq_len)
    )
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * (seq_len - 1) / seq_len
    return 3.0 * (layers + head)


def llama_params(cfg: dict) -> int:
    d, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * i + 2 * d
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return cfg["num_hidden_layers"] * layer + v * d + d + head


def flash_train_work(cfg: dict, batch: int, seq_len: int, itemsize: int = 2) -> dict:
    """What the attention of one training step needs, all layers: the forward's
    two matmuls and the backward's four (dV, dP, dQ, dK; the scores the kernel
    computes again are not counted), and each operand read or written once."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    forward = batch * seq_len * llama_attention_flops_per_token(cfg, seq_len)
    tokens = batch * seq_len
    # forward: read q, k, v, write o; backward: read q, k, v, o, do, write dq, dk, dv
    moved = tokens * itemsize * ((2 * q + 2 * kv) + (4 * q + 4 * kv))
    return {"flops": n * 3.0 * forward, "bytes": n * float(moved)}


# ------------------------------------------------------------------- gpt2 family
def gpt2_layer_matmul_flops_per_token(cfg: dict) -> float:
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    return 2.0 * (4 * d * d + 2 * d * i)


def gpt2_head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def gpt2_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The forward pass over a prompt's real tokens, the head once (only the
    last position's logits are needed)."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    attention = 4.0 * d * mean_keys(prompt_len)
    per_token = n * (gpt2_layer_matmul_flops_per_token(cfg) + attention)
    return prompt_len * per_token + gpt2_head_flops(cfg)


def gpt2_decode_flops(cfg: dict, context_len: float) -> float:
    """One token decoded with ``context_len`` keys in its cache."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return n * (gpt2_layer_matmul_flops_per_token(cfg) + 4.0 * d * context_len) + gpt2_head_flops(cfg)


def gpt2_params(cfg: dict) -> int:
    d, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = 4 * d * d + 4 * d + 2 * d * i + i + d + 4 * d
    return cfg["num_hidden_layers"] * layer + v * d + cfg["max_position_embeddings"] * d + 2 * d


def kv_bytes_per_token_per_layer(cfg: dict, itemsize: int = 2) -> int:
    heads = cfg.get("num_key_value_heads", cfg["num_attention_heads"])
    return 2 * heads * cfg["head_dim"] * itemsize


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one layer, all slots) has
    to read: the keys and values of every live token, once."""
    return live_tokens * kv_bytes_per_token_per_layer(cfg, itemsize)
