"""Counts of the llama family (Llama, Mistral, Qwen2: GQA, SwiGLU, an untied or
tied head): every layer is an attention layer and keeps keys and values. ``cfg``
is a configuration's file as a dict; what these count and what they leave out is
in ``chipbench/work.py``."""

from __future__ import annotations

from chipbench import work


def layer_matmul_flops_per_token(cfg: dict) -> float:
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2.0 * (d * q + 2 * d * kv + q * d + 3 * d * i)


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """QK^T and PV of one layer, forward, for one token of a causal sequence."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * 2.0 * q * work.mean_keys(seq_len, cfg.get("sliding_window"))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (forward x 3) of the layers and the head, a token.
    The head runs on the ``seq_len - 1`` positions that have a label."""
    layers = cfg["num_hidden_layers"] * (
        layer_matmul_flops_per_token(cfg) + attention_flops_per_token(cfg, seq_len)
    )
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * (seq_len - 1) / seq_len
    return 3.0 * (layers + head)


def params(cfg: dict) -> int:
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
    forward = batch * seq_len * attention_flops_per_token(cfg, seq_len)
    tokens = batch * seq_len
    # forward: read q, k, v, write o; backward: read q, k, v, o, do, write dq, dk, dv
    moved = tokens * itemsize * ((2 * q + 2 * kv) + (4 * q + 4 * kv))
    return {"flops": n * 3.0 * forward, "bytes": n * float(moved)}


def kv_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return kv_layers(cfg) * work.kv_bytes_per_token_per_layer(cfg, itemsize)


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one layer, all slots) has
    to read: the keys and values of every live token, once."""
    return live_tokens * work.kv_bytes_per_token_per_layer(cfg, itemsize)
