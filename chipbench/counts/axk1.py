"""Counts of the axk1 family (SKT A.X-K1: multi-head latent attention, group-routed
experts beside a shared one, an untied head), for a chip's share of a layer: the
experts held here (``n_routed_experts`` of ``program_keys.router_experts``), the
vocabulary's slice, attention, the shared expert and the dense layer whole.

Every layer caches one row a token, ``[c_kv | k_r]``: ``kv_lora_rank +
qk_rope_head_dim`` values that the program stores in whole tiles of 128 lanes
(``stored_row``: 640 for 576), and bytes are counted as stored. An expert layer's
FLOPs are those of the shared expert and of the chosen experts held here, at an even
spread ``num_experts_per_tok * held / routed`` of them a token, never of all that
are held. ``W_kvb`` costs a token the same up-projected (a prefill turns the token's
latent into its heads' keys and values) and absorbed (a decode step folds it into
the query and applies it to the result); the scores differ: 192 + 128 a key a head
up-projected, 576 + 512 absorbed. ``cfg`` is a configuration's file as a dict; what
these count and what they leave out is in ``chipbench/work.py``."""

from __future__ import annotations

from chipbench import work


def kv_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def router_experts(cfg: dict) -> int:
    return cfg.get("program_keys", {}).get("router_experts") or cfg["n_routed_experts"]


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


def _expert_weights(cfg: dict) -> int:
    """One expert, routed or shared: a SwiGLU of ``moe_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_experts_per_token(cfg: dict) -> float:
    """Of the experts a token chooses, how many are held here at an even spread."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_experts(cfg)


def matmul_flops_per_token(cfg: dict) -> float:
    """Every layer's matmuls for one token, attention's scores apart."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    experts = moe_layers(cfg) * (
        d * router_experts(cfg)
        + (cfg["n_shared_experts"] + held_experts_per_token(cfg)) * _expert_weights(cfg))
    return 2.0 * (kv_layers(cfg) * _attention_weights(cfg) + dense + experts)


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_attention_flops(cfg: dict, prompt_len: int) -> float:
    """QK^T and PV of one layer's up-projected attention over a prompt: per-head
    keys ``qk_nope + qk_rope`` wide, values ``v_head_dim`` wide."""
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return prompt_len * 2.0 * cfg["num_attention_heads"] * widths * work.mean_keys(prompt_len)


def decode_attention_flops(cfg: dict, keys: float) -> float:
    """The same of one layer's absorbed attention for one query over ``keys``
    cached rows: scores over the row, values its latent part."""
    widths = latent_row(cfg) + cfg["kv_lora_rank"]
    return 2.0 * cfg["num_attention_heads"] * widths * keys


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The forward pass over a prompt's real tokens, the head once."""
    return (prompt_len * matmul_flops_per_token(cfg)
            + kv_layers(cfg) * prefill_attention_flops(cfg, prompt_len) + head_flops(cfg))


def decode_flops(cfg: dict, context_len: float) -> float:
    """One token decoded with ``context_len`` rows in its cache."""
    return (matmul_flops_per_token(cfg)
            + kv_layers(cfg) * decode_attention_flops(cfg, context_len) + head_flops(cfg))


def params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    attention = _attention_weights(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"] + d  # its norms
    dense = 3 * d * cfg["intermediate_size"] + d
    moe = (d * router_experts(cfg) + d
           + (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * _expert_weights(cfg))
    return (kv_layers(cfg) * attention + cfg["first_k_dense_replace"] * dense
            + moe_layers(cfg) * moe + 2 * cfg["vocab_size"] * d + d)  # embedding, head, last norm


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """What one position holds in the cache over all layers, as stored."""
    return kv_layers(cfg) * stored_row(cfg) * itemsize


def paged_decode_bytes(cfg: dict, live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one call of the decode attention kernel (one layer, all slots) has to
    read: every live token's row, as stored (640 values for the 576 that count),
    ONCE: keys and values are the same bytes."""
    return live_tokens * stored_row(cfg) * itemsize


def moe_expert_bytes(cfg: dict, experts_touched: float, rows: float, itemsize: int = 2) -> float:
    """Bytes the grouped matmuls of the expert layers have to move: the three
    matrices of every held expert that got at least one row (``experts_touched``,
    summed over layers and steps), once, and for each of the ``rows`` (a token at
    one of the experts held here) its input read for the two up-projections, their
    float32 results written, the hidden read, and the float32 result written."""
    d, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = experts_touched * _expert_weights(cfg) * itemsize
    per_row = 2 * d * itemsize + 2 * im * 4 + im * itemsize + d * 4
    return weights + rows * per_row
