"""Counts of the gpt2 family (learned positions, LayerNorm with biases, a fused
MLP, a tied head): every layer is an attention layer and keeps keys and values.
``cfg`` is a configuration's file as a dict; what these count and what they leave
out is in ``chipbench/work.py``."""

from __future__ import annotations

from chipbench import work


def layer_matmul_flops_per_token(cfg: dict) -> float:
    d, i = cfg["hidden_size"], cfg["intermediate_size"]
    return 2.0 * (4 * d * d + 2 * d * i)


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The forward pass over a prompt's real tokens, the head once (only the
    last position's logits are needed)."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    attention = 4.0 * d * work.mean_keys(prompt_len)
    per_token = n * (layer_matmul_flops_per_token(cfg) + attention)
    return prompt_len * per_token + head_flops(cfg)


def decode_flops(cfg: dict, context_len: float) -> float:
    """One token decoded with ``context_len`` keys in its cache."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return n * (layer_matmul_flops_per_token(cfg) + 4.0 * d * context_len) + head_flops(cfg)


def params(cfg: dict) -> int:
    d, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = 4 * d * d + 4 * d + 2 * d * i + i + d + 4 * d
    return cfg["num_hidden_layers"] * layer + v * d + cfg["max_position_embeddings"] * d + 2 * d


def kv_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return kv_layers(cfg) * work.kv_bytes_per_token_per_layer(cfg, itemsize)


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one layer, all slots) has
    to read: the keys and values of every live token, once."""
    return live_tokens * work.kv_bytes_per_token_per_layer(cfg, itemsize)
