"""LFM2 (LiquidAI, ``model_type: lfm2_moe``): gated short convolutions beside
grouped-query attention, and sigmoid-routed experts without drops.

A block is ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``. The mixer
of a layer is what ``layer_types`` says, in any order:

* ``conv``: ``B, C, X = split3(u @ W_in)``, ``z = B * X``, a depthwise causal
  convolution of ``conv_L_cache`` taps over ``z``, ``out = (C * conv) @ W_out``.
  No activation. What a sequence carries forward is the last
  ``conv_L_cache - 1`` rows of ``z``, whatever its length.
* ``full_attention``: ``q``, ``k`` RMS-normed over the head (one learned scale
  shared by the heads) before RoPE, causal softmax attention in groups of
  ``num_attention_heads / num_key_value_heads`` query heads a key-value head.

The FFN of the first ``num_dense_layers`` layers is a SwiGLU of
``intermediate_size``; every later layer routes each token to
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of
``moe_intermediate_size`` (:func:`~accelerate_tpu.ops.moe.dropless_moe`). The
head is tied to the token embedding.

The block is written once, over a *cache view*: :class:`_SequenceView` (whole
sequences from position 0: the full forward and the prefill) or
:class:`_StepView` (one new position a row over a cache: decode, dense arena or
paged pool). The parameter tree stacks layers by kind (``attn``, ``conv``,
``dense``, ``moe``); the layer loop is unrolled, each layer reaching its
parameters by its index within its kind and the cache by its index among the
layers that keep that kind of state, so a paged pool is carried whole and
updated in place.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kvcache import attend_step
from ..model import Model
from ..ops.attention import dispatch_attention
from ..ops.moe import dropless_moe
from .family import ServingFamily
from .llama import apply_rope, apply_rope_at, llama_loss, rms_norm

__all__ = [
    "Lfm2Config",
    "create_lfm2",
    "init_lfm2_params",
    "lfm2_apply",
    "lfm2_loss",
    "lfm2_prefill",
    "lfm2_prefill_at",
    "lfm2_decode_step",
]

CONV, ATTENTION = "conv", "full_attention"
# LFM2-8B-A1B's published list: 18 short convolutions, 6 attention layers
_PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24)
)


@dataclasses.dataclass
class Lfm2Config:
    """The published keys under their published names
    (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json)."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Optional[Tuple[str, ...]] = None  # None: the published list's head
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None  # None: hidden_size / num_attention_heads
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.layer_types is None:
            if self.num_hidden_layers > len(_PUBLISHED_LAYER_TYPES):
                raise ValueError(
                    f"layer_types must be given for {self.num_hidden_layers} layers "
                    f"(the published list has {len(_PUBLISHED_LAYER_TYPES)})"
                )
            self.layer_types = _PUBLISHED_LAYER_TYPES[: self.num_hidden_layers]
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types lists {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"unknown layer type(s) {sorted(unknown)}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"num_dense_layers={self.num_dense_layers} of {self.num_hidden_layers}")
        if self.conv_bias:
            raise NotImplementedError("conv_bias=True (the published model has none)")
        if self.conv_L_cache < 2:
            raise ValueError(f"conv_L_cache must be >= 2, got {self.conv_L_cache}")

    # ------------------------------------------------------------ the layout
    @property
    def attention_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def mixer_index(self, layer: int) -> int:
        """``layer``'s place among the layers of its own mixer kind."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def serving_family(self) -> ServingFamily:
        return ServingFamily(
            prefill=lfm2_prefill, prefill_at=lfm2_prefill_at,
            decode_step=lfm2_decode_step,
            # a window of tokens over the cache would have to leave the
            # convolution's state as of the accepted prefix: not written yet
            verify_step=None,
            kv_layers=self.attention_layers, kv_heads=self.num_key_value_heads,
            head_dim=self.head_dim,
            recurrent_layers=self.conv_layers,
            recurrent_shape=(self.conv_L_cache - 1, self.hidden_size),
            step_summary=moe_step_summary,
        )

    # ------------------------------------------------------------------ presets
    @classmethod
    def lfm2_8b_a1b(cls, **overrides) -> "Lfm2Config":
        """LFM2-8B-A1B as published: 24 layers, 8.3B parameters, 1.5B active."""
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "Lfm2Config":
        """Both mixer kinds, a dense layer and expert layers, at test widths."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=5,
            layer_types=(CONV, ATTENTION, CONV, CONV, ATTENTION), num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, max_position_embeddings=512,
        ), **overrides})


# -------------------------------------------------------------------- parameters
# a mixer's and an expert's last matrix, which writes into the residual stream, is
# drawn this much below 1/sqrt(fan_in); the leading dense layers' keeps the full
# scale. At full scale the random model is chaotic, and the routing makes it so:
# a near-tie between two experts that rounding decides the other way replaces an
# expert, which flips more choices downstream (chipbench/reference/lfm2.py)
_RESIDUAL_INIT_SCALE = 0.15


def init_lfm2_params(config: Lfm2Config, key: jax.Array) -> dict:
    """Layers stacked by kind on the first axis. Every matrix is drawn at
    ``1/sqrt(fan_in)`` (``out_proj`` and the experts' ``w2`` at
    ``_RESIDUAL_INIT_SCALE`` of that), the embedding at 0.02, the convolution's
    taps at ``1/sqrt(taps)``, the expert bias at 0.02 (enough to change a choice
    where two scores lie close, too little to starve an expert); norm scales are 1."""
    d, hd = config.hidden_size, config.head_dim
    h, kvh = config.num_attention_heads, config.num_key_value_heads
    a, c = config.attention_layers, config.conv_layers
    nd, nm, e = config.num_dense_layers, config.num_moe_layers, config.num_experts
    i, im, taps = config.intermediate_size, config.moe_intermediate_size, config.conv_L_cache
    dtype = config.param_dtype
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def matrix(lead, fan_in, fan_out, scale=1.0):
        return {"kernel": normal((*lead, fan_in, fan_out), scale / np.sqrt(fan_in))}

    def ones(shape):
        return {"scale": jnp.ones(shape, dtype)}

    return {
        "embed_tokens": {"embedding": normal((config.vocab_size, d), 0.02)},
        "embedding_norm": ones((d,)),
        "attn": {
            "operator_norm": ones((a, d)),
            "q_proj": matrix((a,), d, h * hd),
            "k_proj": matrix((a,), d, kvh * hd),
            "v_proj": matrix((a,), d, kvh * hd),
            "q_layernorm": ones((a, hd)),
            "k_layernorm": ones((a, hd)),
            "out_proj": matrix((a,), h * hd, d, _RESIDUAL_INIT_SCALE),
        },
        "conv": {
            "operator_norm": ones((c, d)),
            "in_proj": matrix((c,), d, 3 * d),
            # (taps, hidden): tap j multiplies the input j - (taps - 1) positions back
            "conv": {"kernel": normal((c, taps, d), 1.0 / np.sqrt(taps))},
            "out_proj": matrix((c,), d, d, _RESIDUAL_INIT_SCALE),
        },
        "dense": {
            "ffn_norm": ones((nd, d)),
            "w1": matrix((nd,), d, i),
            "w3": matrix((nd,), d, i),
            "w2": matrix((nd,), i, d),
        },
        "moe": {
            "ffn_norm": ones((nm, d)),
            "router": matrix((nm,), d, e),
            "expert_bias": normal((nm, e), 0.02),
            "experts": {
                "w1": normal((nm, e, d, im), 1.0 / np.sqrt(d)),
                "w3": normal((nm, e, d, im), 1.0 / np.sqrt(d)),
                "w2": normal((nm, e, im, d), _RESIDUAL_INIT_SCALE / np.sqrt(im)),
            },
        },
    }


# ------------------------------------------------------------------ cache views
class _SequenceView:
    """Whole sequences from position 0, nothing cached before them: the full
    forward and the prefill. With ``last_index`` (B,) it keeps what a cache
    needs: every attention layer's keys and values, and every convolution's
    last inputs as of each row's ``last_index`` (a right-padded prompt's true
    last position: the padded end's state would be wrong)."""

    def __init__(self, config: Lfm2Config, last_index=None):
        self.config, self.last_index = config, last_index
        self.keys, self.values, self.recurrent, self.expert_rows = [], [], [], []

    def conv_window(self, index: int, z):
        """``z`` (B, T, D) with the ``taps - 1`` inputs before it: zeros."""
        back = self.config.conv_L_cache - 1
        window = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))
        if self.last_index is not None:
            # window row r holds input r - back: inputs last-back+1 .. last
            rows = self.last_index[:, None] + 1 + jnp.arange(back)[None, :]
            self.recurrent.append(jnp.take_along_axis(window, rows[:, :, None], axis=1))
        return window

    def attend(self, index: int, q, k, v):
        theta = self.config.rope_theta
        q, k = apply_rope(q, 0, theta), apply_rope(k, 0, theta)
        if self.last_index is not None:
            self.keys.append(k)
            self.values.append(v)
        return dispatch_attention("xla", q, k, v, causal=True)

    def cache(self, max_len: int) -> dict:
        """What was kept, as the cache a decode step takes: keys and values
        padded to ``max_len`` positions."""
        config = self.config
        b = self.last_index.shape[0]
        dtype = config.compute_dtype

        def stacked(parts, empty_shape):
            return jnp.stack(parts) if parts else jnp.zeros((0, *empty_shape), dtype)

        kv_shape = (b, 0, config.num_key_value_heads, config.head_dim)
        keys, values = stacked(self.keys, kv_shape), stacked(self.values, kv_shape)
        pad = ((0, 0), (0, 0), (0, max_len - keys.shape[2]), (0, 0), (0, 0))
        return {
            "k": jnp.pad(keys, pad), "v": jnp.pad(values, pad),
            "recurrent": stacked(
                self.recurrent, (b, config.conv_L_cache - 1, config.hidden_size)
            ).astype(dtype),
        }


class _StepView:
    """One new position a row, at ``pos``, over a cache: ``"k"`` / ``"v"`` hold
    the attention layers' keys and values (a dense ``(A, B, max_len, kv_heads,
    head_dim)`` arena, or with ``kv_layout`` the paged pool, carried whole and
    addressed by ``(attention layer, block)``), ``"recurrent"`` the
    convolutions' last inputs ``(C, B, taps - 1, D)``."""

    def __init__(self, config: Lfm2Config, cache: dict, pos, kv_layout=None):
        self.config, self.pos, self.kv_layout = config, pos, kv_layout
        self.k, self.v, self.before = cache["k"], cache["v"], cache["recurrent"]
        self.recurrent, self.expert_rows = [], []

    def conv_window(self, index: int, z):
        window = jnp.concatenate([self.before[index].astype(z.dtype), z], axis=1)
        self.recurrent.append(window[:, z.shape[1]:])
        return window

    def attend(self, index: int, q, k, v):
        theta = self.config.rope_theta
        q, k = apply_rope_at(q, self.pos, theta), apply_rope_at(k, self.pos, theta)
        out, (self.k, self.v) = attend_step(
            self.kv_layout, (self.k, self.v), index, q, k, v, self.pos
        )
        return out

    def cache(self) -> dict:
        recurrent = jnp.stack(self.recurrent) if self.recurrent else self.before
        return {"k": self.k, "v": self.v, "recurrent": recurrent.astype(self.before.dtype)}


# ------------------------------------------------------------------- the block
def _matmul(config: Lfm2Config, x, kernel):
    """Operands in the compute dtype, the sum kept in float32: what goes on to
    an elementwise step or into the residual stream is not rounded again."""
    cdt = config.compute_dtype
    return jnp.dot(x.astype(cdt), kernel.astype(cdt), preferred_element_type=jnp.float32)


def _conv_mixer(config: Lfm2Config, p: dict, index: int, u, view):
    gate_in, gate_out, x = jnp.split(_matmul(config, u, p["in_proj"]["kernel"][index]), 3, axis=-1)
    # what a sequence carries forward is kept in the compute dtype: rounded
    # here, so that the prefill's window and a decode step's see the same rows
    z = (gate_in * x).astype(config.compute_dtype)
    window = view.conv_window(index, z).astype(jnp.float32)
    taps = p["conv"]["kernel"][index].astype(jnp.float32)  # (taps, D)
    t = z.shape[1]
    conv = sum(taps[j] * window[:, j : j + t] for j in range(config.conv_L_cache))
    return _matmul(config, gate_out * conv, p["out_proj"]["kernel"][index])


def _attention_mixer(config: Lfm2Config, p: dict, index: int, u, view):
    cdt = config.compute_dtype
    b, t, _ = u.shape
    hd = config.head_dim

    def heads(name):
        return _matmul(config, u, p[name]["kernel"][index]).reshape(b, t, -1, hd)

    # the norms see the projections' float32 sums; the kernels and the cache
    # take the compute dtype
    q = rms_norm(heads("q_proj"), p["q_layernorm"]["scale"][index], config.norm_eps).astype(cdt)
    k = rms_norm(heads("k_proj"), p["k_layernorm"]["scale"][index], config.norm_eps).astype(cdt)
    out = view.attend(index, q, k, heads("v_proj").astype(cdt))
    return _matmul(config, out.reshape(b, t, -1), p["out_proj"]["kernel"][index])


def _block(config: Lfm2Config, params: dict, layer: int, x, view):
    """Layer ``layer`` over ``x`` (B, T, D): the one block of the full forward,
    the prefill and the decode step (a window of one). The residual stream
    ``x`` is float32 and so are the norms' results: matmuls round their
    operands to the compute dtype, nothing else is rounded, and the router
    sees the normed hidden state unrounded (a near-tie between two experts
    that rounding decides the other way changes a token's experts, which
    moves its logits by far more than rounding does; PERF.md, PR 30)."""
    kind = config.layer_types[layer]
    index = config.mixer_index(layer)
    if kind == CONV:
        p = params["conv"]
        u = rms_norm(x, p["operator_norm"]["scale"][index], config.norm_eps)
        h = x + _conv_mixer(config, p, index, u, view)
    else:
        p = params["attn"]
        u = rms_norm(x, p["operator_norm"]["scale"][index], config.norm_eps)
        h = x + _attention_mixer(config, p, index, u, view)
    if layer < config.num_dense_layers:
        p = params["dense"]
        u = rms_norm(h, p["ffn_norm"]["scale"][layer], config.norm_eps)
        gate = jax.nn.silu(_matmul(config, u, p["w1"]["kernel"][layer]))
        up = _matmul(config, u, p["w3"]["kernel"][layer])
        return h + _matmul(config, gate * up, p["w2"]["kernel"][layer])
    p = params["moe"]
    index = layer - config.num_dense_layers
    u = rms_norm(h, p["ffn_norm"]["scale"][index], config.norm_eps)
    b, t, d = u.shape
    y, rows = dropless_moe(
        u.reshape(b * t, d), p["router"]["kernel"][index],
        p["expert_bias"][index] if config.use_expert_bias else None,
        p["experts"]["w1"], p["experts"]["w3"], p["experts"]["w2"], layer=index,
        num_selected=config.num_experts_per_tok, norm_topk=config.norm_topk_prob,
        scale=config.routed_scaling_factor, compute_dtype=config.compute_dtype,
    )
    view.expert_rows.append(rows)
    return h + y.reshape(b, t, d)


def _layers(config: Lfm2Config, params: dict, tokens, view):
    """Embedding and every block; the hidden state before the last norm, in
    float32."""
    x = params["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    for layer in range(config.num_hidden_layers):
        x = _block(config, params, layer, x, view)
    return x


def _head(config: Lfm2Config, params: dict, x):
    """The last norm and the tied head: float32 logits over the vocabulary."""
    cdt = config.compute_dtype
    x = rms_norm(x, params["embedding_norm"]["scale"], config.norm_eps).astype(cdt)
    return jnp.einsum(
        "...d,vd->...v", x, params["embed_tokens"]["embedding"].astype(cdt),
        preferred_element_type=jnp.float32,
    )


def _step_counters(config: Lfm2Config, view) -> dict:
    """``moe_rows`` (expert layers, experts): the rows each expert got."""
    rows = view.expert_rows
    return {"moe_rows": jnp.stack(rows) if rows
            else jnp.zeros((0, config.num_experts), jnp.int32)}


def moe_step_summary(counters: dict) -> dict:
    """The scalars a span carries, from one step's ``moe_rows`` on the host."""
    rows = np.asarray(counters["moe_rows"])
    return {
        "moe_assignments": int(rows.sum()),
        "moe_experts_touched": int((rows > 0).sum()),  # summed over the layers
        "moe_expert_slots": int(rows.size),  # layers x experts
        "moe_load_max": int(rows.max()) if rows.size else 0,
    }


# ----------------------------------------------------------------- entry points
def lfm2_apply(config: Lfm2Config, params: dict, input_ids):
    """Full forward, differentiable: ``input_ids`` (B, S) -> float32 logits
    (B, S, V)."""
    view = _SequenceView(config)
    return _head(config, params, _layers(config, params, input_ids, view))


lfm2_loss = llama_loss  # next-token cross entropy over whatever the forward returns


def lfm2_prefill_at(config: Lfm2Config, params: dict, input_ids, max_len: int, last_index):
    """Prefill a right-padded prompt batch: the same forward as
    :func:`lfm2_apply`, logits at each row's ``last_index`` (B,). Returns
    ``(logits (B, V), cache, counters)``: ``cache["k"]`` / ``["v"]`` are the
    attention layers' ``(A, B, max_len, kv_heads, head_dim)``, keys and values
    of padded positions included (decode overwrites a position before it
    attends it), ``cache["recurrent"]`` the convolutions' ``(C, B, taps - 1,
    D)`` as of ``last_index``, which no later step could repair."""
    view = _SequenceView(config, last_index=last_index)
    x = _layers(config, params, input_ids, view)
    last = x[jnp.arange(x.shape[0]), last_index]
    return _head(config, params, last), view.cache(max_len), _step_counters(config, view)


def lfm2_prefill(config: Lfm2Config, params: dict, input_ids, max_len: int):
    """Prefill whole prompts of one length: logits at the last position."""
    b, s = input_ids.shape
    return lfm2_prefill_at(config, params, input_ids, max_len, jnp.full((b,), s - 1, jnp.int32))


def lfm2_decode_step(config: Lfm2Config, params: dict, cache: dict, token, pos, *,
                     kv_layout=None):
    """One token a row: ``token`` (B, 1) at ``pos`` (a traced scalar, or (B,)
    positions of continuous-batching slots) -> ``(logits (B, V), cache,
    counters)``. The block at a window of one over a :class:`_StepView`."""
    view = _StepView(config, cache, pos, kv_layout)
    x = _layers(config, params, token, view)
    return _head(config, params, x[:, 0]), view.cache(), _step_counters(config, view)


def create_lfm2(config: Lfm2Config, seed: int = 0, abstract: bool = False) -> Model:
    """``abstract=True`` gives shapes only (``jax.eval_shape``): whoever brings
    its own weights need not pay for these."""
    init = functools.partial(init_lfm2_params, config)
    params = jax.eval_shape(init, jax.random.key(seed)) if abstract else init(jax.random.key(seed))
    model = Model(functools.partial(lfm2_apply, config), params, name="lfm2")
    model.config = config
    model.canonical_loss = lfm2_loss
    return model
