"""Counts of the lfm2 family (LiquidAI LFM2: gated short convolutions beside
grouped-query attention, sigmoid-routed experts, a tied head). Only the layers
that ``layer_types`` calls ``full_attention`` keep keys and values; a ``conv``
layer keeps ``conv_L_cache - 1`` rows of the hidden size a sequence, whatever its
length. An expert layer's FLOPs are those of the ``num_experts_per_tok`` experts
a token is routed to, never of all of them, whatever the program computes.
``cfg`` is a configuration's file as a dict; what these count and what they leave
out is in ``chipbench/work.py``."""

from __future__ import annotations

from chipbench import work

CONV, ATTENTION = "conv", "full_attention"


def kv_layers(cfg: dict) -> int:
    return cfg["layer_types"].count(ATTENTION)


def conv_layers(cfg: dict) -> int:
    return cfg["layer_types"].count(CONV)


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def _attention_weights(cfg: dict) -> int:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d


def _conv_weights(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def _expert_weights(cfg: dict) -> int:
    """One expert: a SwiGLU of ``moe_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_flops_per_token(cfg: dict) -> float:
    """Every layer's matmuls for one token, attention's scores apart: the mixers,
    the leading dense FFNs, and per expert layer the router and the experts the
    token is routed to; the convolution's taps counted with them."""
    d = cfg["hidden_size"]
    mixers = kv_layers(cfg) * _attention_weights(cfg) + conv_layers(cfg) * (
        _conv_weights(cfg) + cfg["conv_L_cache"] * d)
    dense = cfg["num_dense_layers"] * 3 * d * cfg["intermediate_size"]
    experts = moe_layers(cfg) * (
        d * cfg["num_experts"] + cfg["num_experts_per_tok"] * _expert_weights(cfg))
    return 2.0 * (mixers + dense + experts)


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def _scores_flops(cfg: dict, keys: float) -> float:
    """QK^T and PV of the attention layers for one query over ``keys`` keys."""
    return kv_layers(cfg) * 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The forward pass over a prompt's real tokens, the head once."""
    per_token = matmul_flops_per_token(cfg) + _scores_flops(cfg, work.mean_keys(prompt_len))
    return prompt_len * per_token + head_flops(cfg)


def decode_flops(cfg: dict, context_len: float) -> float:
    """One token decoded with ``context_len`` keys in its cache."""
    return matmul_flops_per_token(cfg) + _scores_flops(cfg, context_len) + head_flops(cfg)


def params(cfg: dict) -> int:
    d, e = cfg["hidden_size"], cfg["num_experts"]
    attention = _attention_weights(cfg) + 2 * cfg["head_dim"] + d  # q/k norms, operator norm
    conv = _conv_weights(cfg) + cfg["conv_L_cache"] * d + d
    dense = 3 * d * cfg["intermediate_size"] + d
    moe = d * e + e + e * _expert_weights(cfg) + d  # router, bias, experts, norm
    return (kv_layers(cfg) * attention + conv_layers(cfg) * conv
            + cfg["num_dense_layers"] * dense + moe_layers(cfg) * moe
            + cfg["vocab_size"] * d + d)  # the embedding (the head is tied) and its norm


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return kv_layers(cfg) * work.kv_bytes_per_token_per_layer(cfg, itemsize)


def recurrent_bytes_per_sequence(cfg: dict, itemsize: int = 2) -> int:
    return conv_layers(cfg) * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one attention layer, all
    slots) has to read: the keys and values of every live token, once."""
    return live_tokens * work.kv_bytes_per_token_per_layer(cfg, itemsize)


def moe_expert_bytes(cfg: dict, experts_touched: float, rows: float, itemsize: int = 2) -> float:
    """Bytes the grouped matmuls of the expert layers have to move: the three
    matrices of every expert that got at least one row (``experts_touched``,
    summed over layers and steps), once, and for each of the ``rows`` (a token
    at one of its experts) its input read for the two up-projections, their
    float32 results written, the hidden read, and the float32 result written."""
    d, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = experts_touched * _expert_weights(cfg) * itemsize
    per_row = 2 * d * itemsize + 2 * im * 4 + im * itemsize + d * 4
    return weights + rows * per_row
