"""LongCat-Flash (Meituan, ``LongcatFlashForCausalLM``): shortcut-connected
mixture-of-experts (ScMoE) double layers over latent (MLA) attention, with
zero-compute experts beside the routed ones.

One layer of ``num_layers`` is a *double layer*: two MLA sublayers, two dense
SwiGLU FFNs of ``ffn_hidden_size`` and one expert block whose input is taken
from the first half of the layer and whose output joins at its end. For the
layer's input ``x``, every norm an RMSNorm:

    h1  = x  + MLA_0(norm_in0(x))
    u   = norm_post0(h1)               # read by the first FFN and by the experts
    m   = MoE(u)                       # the shortcut branch
    h2  = h1 + FFN_0(u)
    h3  = h2 + MLA_1(norm_in1(h2))
    out = h3 + FFN_1(norm_post1(h3)) + m

After the last layer a final RMSNorm and an untied head.

* MLA is A.X-K1's sublayer (:func:`~.axk1.mla_attention`, its two cache views and
  the rotation), here with plain RoPE (``rope_theta``, no scaling; scale
  ``(qk_nope + qk_rope)^-0.5``) and the two latent scales of ``mla_scale_q_lora``
  and ``mla_scale_kv_lora``: ``c_q = sqrt(d / q_lora_rank) RMSNorm(u W_qa)``,
  and the normed key-value latent times ``sqrt(d / kv_lora_rank)`` before
  ``W_kvb`` (the rotary key is not scaled). A position caches the one row
  ``[RMSNorm(c) | k_r]``, unscaled, for each of the two sublayers: the cache has
  ``2 * num_layers`` layers.
* The experts: ``s = softmax(u W_r)`` in float32 over all ``router_experts``
  outputs, ``n_routed_experts`` routed SwiGLU experts of
  ``expert_ffn_hidden_size`` and the last ``zero_expert_num`` zero-compute
  (identity) ones; the ``moe_topk`` best of ``s + e_score_correction_bias`` are
  chosen (the bias for the choice only), each weighted ``s * routed_scaling_factor``
  with no renormalisation: ``MoE(u) = sum_{chosen routed e} w_e E_e(u) +
  (sum_{chosen zero z} w_z) u``. No shared expert.

A chip's share (as A.X-K1's): ``n_routed_experts`` is the routed experts held
here, ``first_expert ..`` of them; the router scores all ``router_experts``
(routed and zero alike) and the held experts add their part
(:func:`~accelerate_tpu.ops.moe.dropless_moe`); the zero-compute experts are
computed on every chip for its own tokens and counted once, as a shared expert
would be. ``vocab_size`` is the rows held here, embedding and head alike.

The parameter tree keeps each attention sublayer and each dense FFN a tree of
its own (``attn.<2l+i>``, ``ffn.<2l+i>``: a layer's slice of a stacked weight is
a copy where the compiler stages it ahead of its matmul, PERF.md, PR 35) and
stacks the experts and the router by layer (the experts go to their kernel as
one stack).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..model import Model
from ..ops.moe import dropless_moe
from . import axk1
from .axk1 import SequenceView, StepView, head_logits, mla_attention, mla_params, swiglu
from .family import ServingFamily
from .llama import llama_loss, rms_norm

__all__ = [
    "LongcatFlashConfig",
    "create_longcat",
    "init_longcat_params",
    "longcat_apply",
    "longcat_loss",
    "longcat_prefill",
    "longcat_prefill_at",
    "longcat_decode_step",
]


@dataclasses.dataclass
class LongcatFlashConfig:
    """The published keys under their published names
    (https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json),
    and the chip's share: ``n_routed_experts`` held of the router's
    ``router_experts`` outputs (routed and zero-compute), from ``first_expert``
    on."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512  # the routed experts held here
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    router_experts: Optional[int] = None  # None: every routed expert held, and the zero ones
    first_expert: int = 0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts + self.zero_expert_num
        if self.zero_expert_type != "identity":
            raise NotImplementedError(f"zero_expert_type={self.zero_expert_type!r}")
        routed = self.router_experts - self.zero_expert_num
        if not 0 <= self.first_expert <= routed - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert} .. {self.first_expert + self.n_routed_experts - 1} "
                f"are not among the router's {routed} routed experts"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim} is odd")

    # ------------------------------------------------------------ the layout
    @property
    def kv_layers(self) -> int:
        """Two latent sublayers a layer, each with its own cached row."""
        return 2 * self.num_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        """``[c_kv | k_r]`` in whole tiles of 128 lanes: 576 stored as 640
        (:attr:`~.axk1.AxK1Config.cache_row_dim`)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def rope_scaling(self) -> None:
        """Plain RoPE: LongCat-Flash-Chat's rotation is unscaled."""
        return None

    @property
    def rope_amplitude(self) -> float:
        return 1.0

    @property
    def latent_scales(self) -> tuple:
        """``sqrt(d / q_lora_rank)`` and ``sqrt(d / kv_lora_rank)``, each where
        its published switch is on."""
        d = self.hidden_size
        return (math.sqrt(d / self.q_lora_rank) if self.mla_scale_q_lora else 1.0,
                math.sqrt(d / self.kv_lora_rank) if self.mla_scale_kv_lora else 1.0)

    def serving_family(self) -> ServingFamily:
        return ServingFamily(
            prefill=longcat_prefill, prefill_at=longcat_prefill_at,
            decode_step=longcat_decode_step,
            # a window over a latent cache: as A.X-K1's (ROADMAP R5)
            verify_step=None,
            kv_layers=self.kv_layers, kv_heads=1,
            head_dim=self.cache_row_dim, value_dim=self.kv_lora_rank,
            step_summary=moe_step_summary,
        )

    # ------------------------------------------------------------------ presets
    @classmethod
    def longcat_flash_chat(cls, **overrides) -> "LongcatFlashConfig":
        """LongCat-Flash-Chat as published: 28 double layers, 560B parameters."""
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "LongcatFlashConfig":
        """Two double layers at test widths, 16 routed and 8 zero-compute
        experts, every routed expert held."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
            num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=32,
            qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_routed_experts=16,
            zero_expert_num=8, moe_topk=4, max_position_embeddings=512,
        ), **overrides})


# -------------------------------------------------------------------- parameters
# the matrices through which a branch writes into the residual stream are drawn
# this much below 1/sqrt(fan_in), as in A.X-K1 and LFM2: at full scale a random
# router's near-ties, decided the other way by rounding, make the random model
# chaotic (PERF.md, PR 30). The first layer's first FFN keeps the full scale and
# sets the stream's size, as A.X-K1's leading dense layer does
_RESIDUAL_INIT_SCALE = 0.15


def init_longcat_params(config: LongcatFlashConfig, key: jax.Array) -> dict:
    """Each attention sublayer and each dense FFN a tree, the router, its
    selection bias and the held experts stacked by layer. Every matrix at
    ``1/sqrt(fan_in)`` (the residual writers at ``_RESIDUAL_INIT_SCALE`` of
    that, ``ffn.0``'s ``w2`` apart), the embedding at 0.02; norm scales are 1,
    the selection bias 0."""
    d, n = config.hidden_size, config.num_layers
    g, e = config.n_routed_experts, config.router_experts
    i, im = config.ffn_hidden_size, config.expert_ffn_hidden_size
    dtype = config.param_dtype
    keys = iter(jax.random.split(key, 8 + 10 * 2 * n))  # 7 draws an attention sublayer, 3 an FFN

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def ffn(index):
        writes = 1.0 if index == 0 else _RESIDUAL_INIT_SCALE
        return {
            "norm": {"scale": jnp.ones((d,), dtype)},
            "w1": {"kernel": normal((d, i), 1.0 / np.sqrt(d))},
            "w3": {"kernel": normal((d, i), 1.0 / np.sqrt(d))},
            "w2": {"kernel": normal((i, d), writes / np.sqrt(i))},
        }

    return {
        "embed_tokens": {"embedding": normal((config.vocab_size, d), 0.02)},
        "norm": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal((d, config.vocab_size), 1.0 / np.sqrt(d))},
        "attn": {str(j): mla_params(config, normal) for j in range(2 * n)},
        "ffn": {str(j): ffn(j) for j in range(2 * n)},
        "moe": {
            "router": {"kernel": normal((n, d, e), 1.0 / np.sqrt(d)),
                       "e_score_correction_bias": jnp.zeros((n, e), dtype)},
            "experts": {
                "w1": normal((n, g, d, im), 1.0 / np.sqrt(d)),
                "w3": normal((n, g, d, im), 1.0 / np.sqrt(d)),
                "w2": normal((n, g, im, d), _RESIDUAL_INIT_SCALE / np.sqrt(im)),
            },
        },
    }


# ------------------------------------------------------------------- the block
def _experts(config: LongcatFlashConfig, p: dict, layer: int, u, view):
    """The shortcut branch: the held experts' part of the routed sum and the
    zero-compute experts' weighted ``u``."""
    b, t, d = u.shape
    out, rows = dropless_moe(
        u.reshape(b * t, d), p["router"]["kernel"][layer],
        p["router"]["e_score_correction_bias"][layer],
        p["experts"]["w1"], p["experts"]["w3"], p["experts"]["w2"], layer=layer,
        first=config.first_expert, num_selected=config.moe_topk, norm_topk=False,
        scale=config.routed_scaling_factor, scoring="softmax",
        zero_experts=config.zero_expert_num, compute_dtype=config.compute_dtype,
    )
    view.expert_rows.append(rows)
    return out.reshape(b, t, d)


def _block(config: LongcatFlashConfig, params: dict, layer: int, x, view):
    """Double layer ``layer`` over ``x`` (B, T, D), the ScMoE order of the module
    docstring: the one block of the full forward, the prefill and the decode
    step. The residual stream and the norms' results are float32; matmuls round
    their operands to the compute dtype, and the router sees ``u`` unrounded."""
    eps = config.rms_norm_eps

    def attention(x, sub):
        p = params["attn"][str(2 * layer + sub)]
        return x + mla_attention(config, p, 2 * layer + sub, rms_norm(x, p["input_norm"]["scale"], eps), view)

    first, second = params["ffn"][str(2 * layer)], params["ffn"][str(2 * layer + 1)]
    h1 = attention(x, 0)
    u = rms_norm(h1, first["norm"]["scale"], eps)
    m = _experts(config, params["moe"], layer, u, view)
    h2 = h1 + swiglu(config, u, first)
    h3 = attention(h2, 1)
    return h3 + swiglu(config, rms_norm(h3, second["norm"]["scale"], eps), second) + m


def _layers(config: LongcatFlashConfig, params: dict, tokens, view):
    """Embedding and every double layer; the hidden state before the last norm,
    in float32."""
    x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    for layer in range(config.num_layers):
        x = _block(config, params, layer, x, view)
    return x


def _step_counters(config: LongcatFlashConfig, view) -> dict:
    """``moe_rows`` (layers, experts held): the rows each held expert got;
    ``moe_rows_elsewhere`` (layers,): the chosen pairs whose routed expert
    another chip holds; ``moe_zero_pairs`` (layers,): the chosen pairs whose
    expert is a zero-compute one."""
    rows = jnp.stack(view.expert_rows)  # (layers, router_experts)
    held = rows[:, config.first_expert: config.first_expert + config.n_routed_experts]
    zero = jnp.sum(rows[:, config.router_experts - config.zero_expert_num:], axis=1)
    return {"moe_rows": held, "moe_zero_pairs": zero,
            "moe_rows_elsewhere": jnp.sum(rows, axis=1) - jnp.sum(held, axis=1) - zero}


def moe_step_summary(counters: dict) -> dict:
    """A.X-K1's scalars of a step (over the routed experts held here, and the
    pairs held elsewhere), with the pairs that chose an identity and every
    chosen (row, expert) pair of all expert layers beside them."""
    out = axk1.moe_step_summary(counters)
    zero = int(np.asarray(counters["moe_zero_pairs"]).sum())
    return {**out, "moe_zero_pairs": zero,
            "moe_pairs": out["moe_assignments"] + out["moe_rows_elsewhere"] + zero}


# ----------------------------------------------------------------- entry points
def longcat_apply(config: LongcatFlashConfig, params: dict, input_ids):
    """Full forward, differentiable: ``input_ids`` (B, S) -> float32 logits
    (B, S, V)."""
    view = SequenceView(config)
    return head_logits(config, params, _layers(config, params, input_ids, view))


longcat_loss = llama_loss  # next-token cross entropy over whatever the forward returns


def longcat_prefill_at(config: LongcatFlashConfig, params: dict, input_ids, max_len: int,
                       last_index):
    """Prefill a right-padded prompt batch, logits at each row's ``last_index``
    (B,). Returns ``(logits (B, V), cache, counters)``: ``cache["k"]`` is every
    latent sublayer's rows ``(2 * num_layers, B, max_len, 1, cache_row_dim)``."""
    view = SequenceView(config, keep=True)
    x = _layers(config, params, input_ids, view)
    last = x[jnp.arange(x.shape[0]), last_index]
    return head_logits(config, params, last), view.cache(max_len), _step_counters(config, view)


def longcat_prefill(config: LongcatFlashConfig, params: dict, input_ids, max_len: int):
    """Prefill whole prompts of one length: logits at the last position."""
    b, s = input_ids.shape
    return longcat_prefill_at(config, params, input_ids, max_len, jnp.full((b,), s - 1, jnp.int32))


def longcat_decode_step(config: LongcatFlashConfig, params: dict, cache: dict, token, pos, *,
                        kv_layout=None):
    """One token a row: ``token`` (B, 1) at ``pos`` (a traced scalar, or (B,)) ->
    ``(logits (B, V), cache, counters)``, attention absorbed over the cache."""
    view = StepView(config, cache, pos, kv_layout)
    x = _layers(config, params, token, view)
    return head_logits(config, params, x[:, 0]), view.cache(), _step_counters(config, view)


def create_longcat(config: LongcatFlashConfig, seed: int = 0, abstract: bool = False) -> Model:
    """``abstract=True`` gives shapes only (``jax.eval_shape``)."""
    init = functools.partial(init_longcat_params, config)
    params = jax.eval_shape(init, jax.random.key(seed)) if abstract else init(jax.random.key(seed))
    model = Model(functools.partial(longcat_apply, config), params, name="longcat")
    model.config = config
    model.canonical_loss = longcat_loss
    return model
