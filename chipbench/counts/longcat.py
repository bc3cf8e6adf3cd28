"""Counts of the longcat family (Meituan LongCat-Flash: shortcut-connected MoE double
layers, two latent (MLA) sublayers and two dense FFNs a layer, softmax-routed
experts beside zero-compute ones, an untied head), for a chip's share of a layer:
the routed experts held here (``n_routed_experts`` of the router's
``program_keys.router_experts`` outputs, the last ``zero_expert_num`` of which are
identities), the vocabulary's slice, attention and the dense FFNs whole.

Each of the two latent sublayers of a layer caches one row a token, ``[c_kv |
k_r]``: ``kv_lora_rank + qk_rope_head_dim`` values stored in whole tiles of 128
lanes (``stored_row``: 640 for 576), and bytes are counted as stored. An expert
layer's FLOPs are those of the routed experts held here that a token chooses at an
even spread (``moe_topk * held / router outputs`` of them), never of all that are
held; a zero-compute expert costs no matmul. Attention is counted as A.X-K1's
(``counts/axk1.py``): up-projected in a prefill, absorbed in a decode step. ``cfg``
is a configuration's file as a dict; what these count and what they leave out is
in ``chipbench/work.py``."""

from __future__ import annotations

from chipbench import work


def kv_layers(cfg: dict) -> int:
    """Two latent sublayers a double layer, a row each."""
    return 2 * cfg["num_layers"]


def router_experts(cfg: dict) -> int:
    """The router's outputs: routed experts and zero-compute ones."""
    return (cfg.get("program_keys", {}).get("router_experts")
            or cfg["n_routed_experts"] + cfg["zero_expert_num"])


def latent_row(cfg: dict) -> int:
    """Values of a cached row: the normed latent and the rotated shared key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def stored_row(cfg: dict) -> int:
    """The same as the program stores it: whole tiles of 128 lanes."""
    return -(-latent_row(cfg) // 128) * 128


def _attention_weights(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * rq + rq * h * (nope + rope) + d * (rkv + rope) + rkv * h * (nope + vd) + h * vd * d


def _ffn_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def _expert_weights(cfg: dict) -> int:
    """One routed expert: a SwiGLU of ``expert_ffn_hidden_size``."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def held_experts_per_token(cfg: dict) -> float:
    """Of the experts a token chooses, how many are routed experts held here, at an
    even spread over the router's outputs."""
    return cfg["moe_topk"] * cfg["n_routed_experts"] / router_experts(cfg)


def matmul_flops_per_token(cfg: dict) -> float:
    """Every layer's matmuls for one token, attention's scores apart: two latent
    sublayers, two dense FFNs, the router and the held experts a token reaches."""
    d = cfg["hidden_size"]
    layer = (2 * _attention_weights(cfg) + 2 * _ffn_weights(cfg) + d * router_experts(cfg)
             + held_experts_per_token(cfg) * _expert_weights(cfg))
    return 2.0 * cfg["num_layers"] * layer


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_attention_flops(cfg: dict, prompt_len: int) -> float:
    """QK^T and PV of one latent sublayer's up-projected attention over a prompt:
    per-head keys ``qk_nope + qk_rope`` wide, values ``v_head_dim`` wide."""
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return prompt_len * 2.0 * cfg["num_attention_heads"] * widths * work.mean_keys(prompt_len)


def decode_attention_flops(cfg: dict, keys: float) -> float:
    """The same of one sublayer's absorbed attention for one query over ``keys``
    cached rows: scores over the row, values its latent part."""
    return 2.0 * cfg["num_attention_heads"] * (latent_row(cfg) + cfg["kv_lora_rank"]) * keys


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The forward pass over a prompt's real tokens, the head once."""
    return (prompt_len * matmul_flops_per_token(cfg)
            + kv_layers(cfg) * prefill_attention_flops(cfg, prompt_len) + head_flops(cfg))


def decode_flops(cfg: dict, context_len: float) -> float:
    """One token decoded with ``context_len`` rows in its cache."""
    return (matmul_flops_per_token(cfg)
            + kv_layers(cfg) * decode_attention_flops(cfg, context_len) + head_flops(cfg))


def params(cfg: dict) -> int:
    d, e = cfg["hidden_size"], router_experts(cfg)
    attention = _attention_weights(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"] + d  # its norms
    layer = (2 * attention + 2 * (_ffn_weights(cfg) + d) + d * e + e  # router and its bias
             + cfg["n_routed_experts"] * _expert_weights(cfg))
    return cfg["num_layers"] * layer + 2 * cfg["vocab_size"] * d + d  # embedding, head, last norm


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one position holds in the cache over all latent sublayers, as stored."""
    return kv_layers(cfg) * stored_row(cfg) * itemsize


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one sublayer, all slots) has
    to read: every live token's row, as stored, ONCE: keys and values are the same
    bytes."""
    return live_tokens * stored_row(cfg) * itemsize


def moe_expert_bytes(cfg: dict, experts_touched: float, rows: float, itemsize: int = 2) -> float:
    """Bytes the grouped matmuls of the expert layers have to move: the three
    matrices of every held expert that got at least one row (``experts_touched``,
    summed over layers and steps), once, and for each of the ``rows`` (a token at
    one of the experts held here) its input read for the two up-projections, their
    float32 results written, the hidden read, and the float32 result written. A
    zero-compute expert's pairs reach no grouped matmul."""
    d, im = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    weights = experts_touched * _expert_weights(cfg) * itemsize
    per_row = 2 * d * itemsize + 2 * im * 4 + im * itemsize + d * 4
    return weights + rows * per_row
