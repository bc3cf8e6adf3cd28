"""A.X-K1 (SKT, ``model_type: axk1``): multi-head latent attention (MLA) over a
compressed cache, and group-routed experts beside a shared one.

A block is ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after the
last block a final RMSNorm and an untied head. With ``u`` the normed input:

* ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb``, a head ``[q_nope | q_r]``, ``q_r``
  rotated (``W_qb``'s columns are kept apart by what they make, ``q_b_nope`` and
  ``q_b_rope``, outputs on the rows; ``W_kvb``'s as ``kv_b_k`` and ``kv_b_v``, a
  head at a time). ``[c | k_r] = u W_kva``; ``c_kv = RMSNorm(c)``; ``k_r`` rotated, one
  key shared by all heads. **What a position caches is the one row ``[c_kv |
  k_r]``** (``kv_lora_rank + qk_rope_head_dim`` wide, stored in whole tiles of
  128 lanes: :attr:`AxK1Config.cache_row_dim`), never its heads' keys and
  values.
* ``[k_nope_i | v_i] = c_kv W_kvb`` for head ``i``; ``s_i = (q_nope_i . k_nope_i
  + q_r_i . k_r) * scale``, causal softmax, ``o_i = softmax(s_i) v_i``, ``Attn =
  concat(o_i) W_o``. That order, *up-projected*, is the full forward's and the
  prefill's (:class:`_SequenceView`): per-head keys ``qk_nope + qk_rope`` wide,
  values ``v_head_dim`` wide, over the prompt itself.
* A decode step computes the same *absorbed* (:class:`_StepView`): ``q~_i =
  q_nope_i (W_kvb^K_i)^T`` (as wide as ``c_kv``), ``s_i = (q~_i . c_kv + q_r_i .
  k_r) * scale``, ``o~_i = softmax(s_i) c_kv``, ``o_i = o~_i W_kvb^V_i``: the
  step reads only cached rows, each once, as keys and (its first
  ``kv_lora_rank`` columns) as values.
* Rotary: YaRN's blend of the inverse frequencies (:func:`yarn_inv_freq`),
  pairs interleaved; ``scale = (qk_nope + qk_rope)^-0.5 * m^2`` with ``m`` from
  ``mscale_all_dim`` (:meth:`AxK1Config.softmax_scale`).

The FFN of the first ``first_k_dense_replace`` layers is a SwiGLU of
``intermediate_size``. Every later layer: ``s = sigmoid(u W_r)`` over all
``router_experts``; the experts lie in ``n_group`` groups, a group's score is
the sum of its two best ``s``, the ``topk_group`` best groups stay, the
``num_experts_per_tok`` best ``s`` inside them are chosen, ``w = s / (sum s +
1e-20) * routed_scaling_factor``; ``FFN = Shared(u) + sum_j w_j E_j(u)``.

A chip's share of a layer. ``n_routed_experts`` is the experts *held here*,
experts ``first_expert .. first_expert + n_routed_experts - 1`` of the
``router_experts`` the router scores (default: all of them held). Routing runs
over all of them; the held ones add their part, the others add nothing
(:func:`~accelerate_tpu.ops.moe.dropless_moe`), and the shared expert is
computed whole: the shares of a layer split over chips, the shared expert
counted once, add up to the layer. ``vocab_size`` is the rows of the vocabulary
held here, embedding and head alike: ids and logits are over the slice.

The block is written once, over a *cache view*; the parameter tree keeps
attention's parameters a layer (``attn.<layer>``) and stacks the dense and the
expert layers by kind (the experts go to their kernel as one stack), and the
layer loop is unrolled, so a paged pool is carried whole and updated in place.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..kvcache import attend_step
from ..model import Model
from ..ops.attention import dot_product_attention
from ..ops.moe import dropless_moe
from .family import ServingFamily
from .llama import llama_loss, rms_norm

__all__ = [
    "AxK1Config",
    "create_axk1",
    "init_axk1_params",
    "axk1_apply",
    "axk1_loss",
    "axk1_prefill",
    "axk1_prefill_at",
    "axk1_decode_step",
    "yarn_inv_freq",
]

_PUBLISHED_ROPE_SCALING = {
    "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
    "original_max_position_embeddings": 4096, "type": "yarn",
}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies of a rotary embedding under YaRN
    (the DeepSeek-V2/V3 convention): ``theta^(-2j/dim)`` for the dimensions that
    turn more than ``beta_fast`` times over the original positions, the same
    over ``factor`` for those that turn fewer than ``beta_slow`` times, and a
    linear ramp between the two dimensions those turns name."""
    extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not scaling:
        return extra
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {scaling!r}: only yarn is written")
    factor, original = float(scaling["factor"]), scaling["original_max_position_embeddings"]

    def dimension_of(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0, 1)
    return (extra / factor) * ramp + extra * (1 - ramp)


@dataclasses.dataclass
class AxK1Config:
    """The published keys under their published names
    (https://huggingface.co/skt/A.X-K1/blob/main/config.json), and the chip's
    share: ``n_routed_experts`` held of ``router_experts``, from
    ``first_expert`` on."""

    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192  # the experts held here
    router_experts: Optional[int] = None  # None: n_routed_experts, all of them held
    first_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = dataclasses.field(
        default_factory=lambda: dict(_PUBLISHED_ROPE_SCALING))
    max_position_embeddings: int = 131072
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if not 0 <= self.first_expert <= self.router_experts - self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert} .. {self.first_expert + self.n_routed_experts - 1} "
                f"are not among the router's {self.router_experts}"
            )
        if self.router_experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.router_experts} experts in {self.n_group} groups, {self.topk_group} kept")
        if self.num_experts_per_tok > self.topk_group * (self.router_experts // self.n_group):
            raise ValueError("fewer experts in the kept groups than a token chooses")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace={self.first_k_dense_replace} of {self.num_hidden_layers}")
        if self.n_shared_experts not in (0, 1):
            raise NotImplementedError(f"n_shared_experts={self.n_shared_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim} is odd")

    # ------------------------------------------------------------ the layout
    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        """What a position caches a layer: ``[c_kv | k_r]``, with zeros up to
        whole tiles of 128 lanes. The chip lays a row of 576 out 640 wide in
        HBM whatever its shape says, and the decode kernel's copies of a block
        must be whole tiles (its compiler refuses a slice of 576: PERF.md, PR
        35), so the row is stored at the width it occupies."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_scaling:
            m = _yarn_mscale(self.rope_scaling["factor"], self.rope_scaling.get("mscale_all_dim", 0))
            scale *= m * m
        return scale

    @property
    def rope_amplitude(self) -> float:
        """The factor on cos and sin: 1 where ``mscale == mscale_all_dim``."""
        if not self.rope_scaling:
            return 1.0
        factor = self.rope_scaling["factor"]
        return (_yarn_mscale(factor, self.rope_scaling.get("mscale", 1))
                / _yarn_mscale(factor, self.rope_scaling.get("mscale_all_dim", 0)))

    def serving_family(self) -> ServingFamily:
        return ServingFamily(
            prefill=axk1_prefill, prefill_at=axk1_prefill_at,
            decode_step=axk1_decode_step,
            # a window of tokens over a latent cache: neither the seam's
            # attend_window nor paged_flash_verify reads one-leaf rows yet
            verify_step=None,
            kv_layers=self.num_hidden_layers, kv_heads=1,
            head_dim=self.cache_row_dim, value_dim=self.kv_lora_rank,
            step_summary=moe_step_summary,
        )

    # ------------------------------------------------------------------ presets
    @classmethod
    def ax_k1(cls, **overrides) -> "AxK1Config":
        """A.X-K1 as published: 61 layers, 519B parameters."""
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "AxK1Config":
        """A dense layer and two expert layers at test widths, every expert held."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, n_group=4, topk_group=2, num_experts_per_tok=4,
            rope_scaling={"beta_fast": 4, "beta_slow": 1, "factor": 4, "mscale": 1,
                          "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                          "type": "yarn"},
            max_position_embeddings=512,
        ), **overrides})


# -------------------------------------------------------------------- parameters
# the matrices through which a branch writes into the residual stream (``o_proj``,
# every expert's and the shared expert's ``w2``) are drawn this much below
# 1/sqrt(fan_in); the leading dense layers' ``w2`` keeps the full scale and sets
# the stream's size. At full scale a random router's near-ties, decided the other
# way by rounding, make the random model chaotic (models/lfm2.py; PERF.md, PR 30)
_RESIDUAL_INIT_SCALE = 0.15


def init_axk1_params(config: AxK1Config, key: jax.Array) -> dict:
    """Attention a layer, dense and expert layers stacked by kind on the first
    axis. Every matrix is drawn at
    ``1/sqrt(fan_in)`` (the residual writers at ``_RESIDUAL_INIT_SCALE`` of
    that), the embedding at 0.02; norm scales are 1."""
    d, h = config.hidden_size, config.num_attention_heads
    n, nd, nm = config.num_hidden_layers, config.first_k_dense_replace, config.num_moe_layers
    g, e = config.n_routed_experts, config.router_experts
    i, im = config.intermediate_size, config.moe_intermediate_size
    ims = im * config.n_shared_experts
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    dtype = config.param_dtype
    keys = iter(jax.random.split(key, 16 + 8 * n))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def matrix(lead, fan_in, fan_out, scale=1.0):
        return {"kernel": normal((*lead, fan_in, fan_out), scale / np.sqrt(fan_in))}

    def ones(shape):
        return {"scale": jnp.ones(shape, dtype)}

    def attention_layer():
        return {
            "input_norm": ones((d,)),
            "q_a": matrix((), d, rq),
            "q_a_norm": ones((rq,)),
            # W_qb's and W_kvb's columns apart by what they make (a head's q_nope
            # and q_r; its k_nope and v), W_qb's and the value half's outputs on
            # the rows and W_kvb a head at a time: one matrix cut by columns
            # inside a step, or read in another order than it lies in, is a copy
            # of it every step (a decode step copied 0.53e9 B of them)
            "q_b_nope": {"kernel": normal((h * config.qk_nope_head_dim, rq), 1 / np.sqrt(rq))},
            "q_b_rope": {"kernel": normal((h * config.qk_rope_head_dim, rq), 1 / np.sqrt(rq))},
            "kv_a": matrix((), d, rkv + config.qk_rope_head_dim),
            "kv_a_norm": ones((rkv,)),
            "kv_b_k": matrix((h,), rkv, config.qk_nope_head_dim),
            "kv_b_v": {"kernel": normal((h, config.v_head_dim, rkv), 1 / np.sqrt(rkv))},
            "o_proj": matrix((), h * config.v_head_dim, d, _RESIDUAL_INIT_SCALE),
        }

    return {
        "embed_tokens": {"embedding": normal((config.vocab_size, d), 0.02)},
        "norm": ones((d,)),
        "lm_head": matrix((), d, config.vocab_size),
        # attention's parameters a layer, not stacked: a layer's slice of a stack
        # is a copy where the compiler stages that weight ahead of its matmul,
        # and it made one of every layer's, every step (PERF.md, PR 35)
        "attn": {str(layer): attention_layer() for layer in range(n)},
        "dense": {
            "ffn_norm": ones((nd, d)),
            "w1": matrix((nd,), d, i),
            "w3": matrix((nd,), d, i),
            "w2": matrix((nd,), i, d),
        },
        "moe": {
            "ffn_norm": ones((nm, d)),
            "router": matrix((nm,), d, e),
            "experts": {
                "w1": normal((nm, g, d, im), 1.0 / np.sqrt(d)),
                "w3": normal((nm, g, d, im), 1.0 / np.sqrt(d)),
                "w2": normal((nm, g, im, d), _RESIDUAL_INIT_SCALE / np.sqrt(im)),
            },
            "shared": {
                "w1": matrix((nm,), d, ims),
                "w3": matrix((nm,), d, ims),
                "w2": matrix((nm,), ims, d, _RESIDUAL_INIT_SCALE),
            },
        },
    }


# ------------------------------------------------------------------ the rotation
def _rope(config: AxK1Config, x, positions):
    """``x`` (B, T, heads, qk_rope_head_dim) rotated, pairs interleaved, row
    ``t`` of batch row ``b`` at ``positions`` (T,) or (B, T)."""
    inv_freq = jnp.asarray(
        yarn_inv_freq(config.qk_rope_head_dim, config.rope_theta, config.rope_scaling), jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (T, d/2) or (B, T, d/2)
    amplitude = config.rope_amplitude
    cos = (jnp.cos(angles) * amplitude)[..., None, :]
    sin = (jnp.sin(angles) * amplitude)[..., None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _padded_row(config: AxK1Config, latent, rope):
    """``[latent | rope | 0 ..]``, ``cache_row_dim`` wide: a cached row, or the
    absorbed query that meets it."""
    pad = config.cache_row_dim - latent.shape[-1] - rope.shape[-1]
    return jnp.concatenate(
        [latent, rope, jnp.zeros((*latent.shape[:-1], pad), latent.dtype)], axis=-1)


# ------------------------------------------------------------------ cache views
class _SequenceView:
    """Whole sequences from position 0, nothing cached before them: the full
    forward and the prefill, attention up-projected. With ``keep`` it keeps what
    a cache needs: every layer's rows ``[c_kv | k_r]``."""

    def __init__(self, config: AxK1Config, keep: bool = False):
        self.config, self.keep = config, keep
        self.rows, self.expert_rows = [], []

    def positions(self, t: int):
        return jnp.arange(t)

    def attend(self, index: int, q_nope, q_rope, row, w_k, w_v):
        config, cdt = self.config, self.config.compute_dtype
        if self.keep:
            self.rows.append(row)
        b, t, h, _ = q_nope.shape
        c_kv = row[..., : config.kv_lora_rank]
        k_rope = row[..., config.kv_lora_rank: config.kv_lora_rank + config.qk_rope_head_dim]
        w_k, w_v = w_k.astype(cdt), w_v.astype(cdt)  # (h, rkv, nope), (h, v, rkv)
        k_nope = jnp.einsum("btc,hcn->bthn", c_kv[:, :, 0], w_k, preferred_element_type=jnp.float32)
        v = jnp.einsum("btc,hnc->bthn", c_kv[:, :, 0], w_v, preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope.astype(cdt), jnp.broadcast_to(k_rope, (b, t, h, k_rope.shape[-1]))], axis=-1)
        return dot_product_attention(q, k, v.astype(cdt), causal=True, scale=config.softmax_scale)

    def cache(self, max_len: int) -> dict:
        rows = jnp.stack(self.rows)  # (layers, B, T, 1, row)
        pad = ((0, 0), (0, 0), (0, max_len - rows.shape[2]), (0, 0), (0, 0))
        return {"k": jnp.pad(rows, pad)}


class _StepView:
    """One new position a row, at ``pos``, over a cache of rows ``"k"`` (a dense
    ``(layers, B, max_len, 1, row)`` arena, or with ``kv_layout`` the paged pool,
    carried whole): attention absorbed, the step reads only cached rows."""

    def __init__(self, config: AxK1Config, cache: dict, pos, kv_layout=None):
        self.config, self.pos, self.kv_layout = config, pos, kv_layout
        self.rows, self.expert_rows = cache["k"], []

    def positions(self, t: int):
        return self.pos[..., None] + jnp.arange(t)  # (1,) or (B, 1): t is 1

    def attend(self, index: int, q_nope, q_rope, row, w_k, w_v):
        config, cdt = self.config, self.config.compute_dtype
        w_k, w_v = w_k.astype(cdt), w_v.astype(cdt)  # (h, rkv, nope), (h, v, rkv)
        q_latent = jnp.einsum("bthn,hcn->bthc", q_nope, w_k, preferred_element_type=jnp.float32)
        q = _padded_row(config, q_latent.astype(cdt), q_rope)  # as wide as a row
        out, (self.rows, _) = attend_step(
            self.kv_layout, (self.rows, None), index, q, row, None, self.pos,
            scale=config.softmax_scale, value_dim=config.kv_lora_rank,
        )
        return jnp.einsum(
            "bthc,hnc->bthn", out.astype(cdt), w_v, preferred_element_type=jnp.float32
        ).astype(cdt)

    def cache(self) -> dict:
        return {"k": self.rows}


# ------------------------------------------------------------------- the block
def _matmul(config: AxK1Config, x, kernel):
    """Operands in the compute dtype, the sum kept in float32: what goes on to
    an elementwise step or into the residual stream is not rounded again."""
    cdt = config.compute_dtype
    return jnp.dot(x.astype(cdt), kernel.astype(cdt), preferred_element_type=jnp.float32)


def _attention(config: AxK1Config, p: dict, layer: int, u, view):
    # ``p``: this layer's own parameters
    """The query's two parts and the position's new row, handed to the view:
    what is cached and in which order the products are taken is the view's."""
    cdt, eps = config.compute_dtype, config.rms_norm_eps
    b, t, _ = u.shape
    h, rkv = config.num_attention_heads, config.kv_lora_rank
    positions = view.positions(t)
    c_q = rms_norm(_matmul(config, u, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    def up(name):  # W_qb's rows are its outputs: (heads * width, q_lora_rank)
        return jnp.einsum("btr,fr->btf", c_q.astype(cdt), p[name]["kernel"].astype(cdt),
                          preferred_element_type=jnp.float32).reshape(b, t, h, -1)

    q_nope, q_rope = up("q_b_nope").astype(cdt), up("q_b_rope")
    q_rope = _rope(config, q_rope, positions).astype(cdt)
    ckr = _matmul(config, u, p["kv_a"]["kernel"])  # (B, T, rkv + rope), float32
    c_kv = rms_norm(ckr[..., :rkv], p["kv_a_norm"]["scale"], eps)
    k_rope = _rope(config, ckr[:, :, None, rkv:], positions)
    # the row as it is cached, in the compute dtype: the prefill's attention and
    # a decode step's see the same rounded values
    row = _padded_row(config, c_kv[:, :, None, :].astype(cdt), k_rope.astype(cdt))
    out = view.attend(layer, q_nope, q_rope, row,
                      p["kv_b_k"]["kernel"], p["kv_b_v"]["kernel"])
    return _matmul(config, out.reshape(b, t, h * config.v_head_dim), p["o_proj"]["kernel"])


def _swiglu(config: AxK1Config, u, p: dict, index: int):
    gate = jax.nn.silu(_matmul(config, u, p["w1"]["kernel"][index]))
    up = _matmul(config, u, p["w3"]["kernel"][index])
    return _matmul(config, gate * up, p["w2"]["kernel"][index])


def _experts(config: AxK1Config, p: dict, index: int, u, view):
    """The shared expert, whole, and the held experts' part of the routed sum."""
    b, t, d = u.shape
    routed, rows = dropless_moe(
        u.reshape(b * t, d), p["router"]["kernel"][index], None,
        p["experts"]["w1"], p["experts"]["w3"], p["experts"]["w2"], layer=index,
        first=config.first_expert, num_selected=config.num_experts_per_tok,
        norm_topk=config.norm_topk_prob, norm_eps=1e-20,
        scale=config.routed_scaling_factor, n_group=config.n_group,
        topk_group=config.topk_group, compute_dtype=config.compute_dtype,
    )
    view.expert_rows.append(rows)
    routed = routed.reshape(b, t, d)
    if not config.n_shared_experts:
        return routed
    return _swiglu(config, u, p["shared"], index) + routed


def _block(config: AxK1Config, params: dict, layer: int, x, view):
    """Layer ``layer`` over ``x`` (B, T, D): the one block of the full forward,
    the prefill and the decode step (a window of one). The residual stream and
    the norms' results are float32; matmuls round their operands to the compute
    dtype, and the router sees the normed hidden state unrounded."""
    eps = config.rms_norm_eps
    p = params["attn"][str(layer)]
    u = rms_norm(x, p["input_norm"]["scale"], eps)
    h = x + _attention(config, p, layer, u, view)
    if layer < config.first_k_dense_replace:
        p = params["dense"]
        u = rms_norm(h, p["ffn_norm"]["scale"][layer], eps)
        return h + _swiglu(config, u, p, layer)
    p = params["moe"]
    index = layer - config.first_k_dense_replace
    u = rms_norm(h, p["ffn_norm"]["scale"][index], eps)
    return h + _experts(config, p, index, u, view)


def _layers(config: AxK1Config, params: dict, tokens, view):
    """Embedding and every block; the hidden state before the last norm, in
    float32."""
    x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    for layer in range(config.num_hidden_layers):
        x = _block(config, params, layer, x, view)
    return x


def _head(config: AxK1Config, params: dict, x):
    """The last norm and the untied head: float32 logits over the vocabulary
    held here."""
    x = rms_norm(x, params["norm"]["scale"], config.rms_norm_eps)
    return _matmul(config, x, params["lm_head"]["kernel"])


def _step_counters(config: AxK1Config, view) -> dict:
    """``moe_rows`` (expert layers, experts held): the rows each held expert
    got; ``moe_rows_elsewhere`` (expert layers,): the chosen (row, expert) pairs
    whose expert another chip holds."""
    if not view.expert_rows:
        return {"moe_rows": jnp.zeros((0, config.n_routed_experts), jnp.int32),
                "moe_rows_elsewhere": jnp.zeros((0,), jnp.int32)}
    rows = jnp.stack(view.expert_rows)  # (expert layers, router_experts)
    held = rows[:, config.first_expert: config.first_expert + config.n_routed_experts]
    return {"moe_rows": held, "moe_rows_elsewhere": jnp.sum(rows, axis=1) - jnp.sum(held, axis=1)}


def moe_step_summary(counters: dict) -> dict:
    """The scalars a span carries, from one step's counters on the host: all
    but the last over the experts held here."""
    rows = np.asarray(counters["moe_rows"])
    return {
        "moe_assignments": int(rows.sum()),  # rows that reached a held expert
        "moe_experts_touched": int((rows > 0).sum()),  # summed over the layers
        "moe_expert_slots": int(rows.size),  # layers x experts held
        "moe_load_max": int(rows.max()) if rows.size else 0,
        "moe_rows_elsewhere": int(np.asarray(counters["moe_rows_elsewhere"]).sum()),
    }


# ----------------------------------------------------------------- entry points
def axk1_apply(config: AxK1Config, params: dict, input_ids):
    """Full forward, differentiable: ``input_ids`` (B, S) -> float32 logits
    (B, S, V)."""
    view = _SequenceView(config)
    return _head(config, params, _layers(config, params, input_ids, view))


axk1_loss = llama_loss  # next-token cross entropy over whatever the forward returns


def axk1_prefill_at(config: AxK1Config, params: dict, input_ids, max_len: int, last_index):
    """Prefill a right-padded prompt batch: the same forward as
    :func:`axk1_apply`, logits at each row's ``last_index`` (B,). Returns
    ``(logits (B, V), cache, counters)``: ``cache["k"]`` is every layer's rows
    ``(layers, B, max_len, 1, cache_row_dim)``, padded
    positions' included (decode overwrites a position before it attends it);
    there is no ``"v"``."""
    view = _SequenceView(config, keep=True)
    x = _layers(config, params, input_ids, view)
    last = x[jnp.arange(x.shape[0]), last_index]
    return _head(config, params, last), view.cache(max_len), _step_counters(config, view)


def axk1_prefill(config: AxK1Config, params: dict, input_ids, max_len: int):
    """Prefill whole prompts of one length: logits at the last position."""
    b, s = input_ids.shape
    return axk1_prefill_at(config, params, input_ids, max_len, jnp.full((b,), s - 1, jnp.int32))


def axk1_decode_step(config: AxK1Config, params: dict, cache: dict, token, pos, *,
                     kv_layout=None):
    """One token a row: ``token`` (B, 1) at ``pos`` (a traced scalar, or (B,)
    positions of continuous-batching slots) -> ``(logits (B, V), cache,
    counters)``. The block at a window of one over a :class:`_StepView`."""
    view = _StepView(config, cache, pos, kv_layout)
    x = _layers(config, params, token, view)
    return _head(config, params, x[:, 0]), view.cache(), _step_counters(config, view)


def create_axk1(config: AxK1Config, seed: int = 0, abstract: bool = False) -> Model:
    """``abstract=True`` gives shapes only (``jax.eval_shape``): whoever brings
    its own weights need not pay for these."""
    init = functools.partial(init_axk1_params, config)
    params = jax.eval_shape(init, jax.random.key(seed)) if abstract else init(jax.random.key(seed))
    model = Model(functools.partial(axk1_apply, config), params, name="axk1")
    model.config = config
    model.canonical_loss = axk1_loss
    return model
