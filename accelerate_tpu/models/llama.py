"""Llama-family decoder, TPU-first.

The flagship workload for the FSDP2 Llama-2-7B north-star benchmark
(BASELINE.json; reference benchmarks/fsdp2/main.py fine-tunes Llama-2-7B).
Built for XLA, not ported:

* **scan over layers** — one compiled layer body, stacked params (L, ...):
  compile time O(1) in depth, and the pattern XLA pipelines best;
* **remat** — ``jax.checkpoint`` on the layer body with a selectable policy
  (``LlamaConfig.remat_policy``; :func:`checkpoint_layer` is the one place a
  layer loop reads it);
* bf16 compute / fp32 master params; RMSNorm + rotary + SwiGLU + GQA;
* attention implementation is injectable: "xla" (materialized), "blockwise"
  (online softmax), "flash" (Pallas kernel), or "ring"/"ulysses" wired by the
  CP/SP preparers.

Sharding: parameter names match parallel/tp.py rules (q_proj/k_proj/... →
column, o_proj/down_proj → row); stacked layer params put the layer dim first
so the FSDP heuristic shards hidden dims, never the scan dim.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..kvcache import attend_step, attend_window, scan_layers
from ..model import Model
from ..parallel.sharding import (
    constrain_activation,
    gather_over_fsdp,
    replicate_over_fsdp,
)

__all__ = ["LlamaConfig", "init_llama_params", "llama_apply", "create_llama", "llama_loss"]


@dataclasses.dataclass
class LlamaConfig:
    """A Llama-family decoder (Llama, Mistral, Qwen2, Gemma, Mixtral).

    ``remat_policy`` says what a layer saves for the backward pass
    (:func:`checkpoint_layer`), from most memory and no recompute to least
    memory and a whole second forward:

    * ``"full"`` — no checkpoint: every intermediate is saved;
    * ``"dots"`` — matmul outputs are saved, the flash kernel's among them,
      and the rest is recomputed (``"dots_no_batch"``: only plain matmuls
      without batch dimensions);
    * ``"minimal"`` — the two block outputs a layer are saved;
    * ``"nothing"`` — only a layer's input is saved;
    * ``"auto"`` (the default) — ``Accelerator.train_step`` keeps the first
      of dots, full, minimal, nothing (``REMAT_LADDER``, fastest first) whose
      compiled step fits the device's memory; anywhere else, and where the
      backend reports no memory limit, it is ``"nothing"``.
    """

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # Mistral-style sliding-window attention: each query attends the last W
    # keys only (None = full causal). The flash kernel grid-prunes
    # out-of-window kv tiles, so long-seq compute is O(S·W) per row.
    sliding_window: Optional[int] = None
    # Qwen2-style biases on the q/k/v projections (o_proj stays bias-free)
    attention_bias: bool = False
    # RoPE scaling for beyond-pretraining context (HF rope_scaling dict):
    #   {"rope_type": "linear", "factor": f}  — all frequencies / f
    #   {"rope_type": "llama3", "factor": f, "low_freq_factor": ...,
    #    "high_freq_factor": ..., "original_max_position_embeddings": ...}
    #     — Llama-3.1 wavelength-dependent scaling
    rope_scaling: Optional[dict] = None
    # Gemma-family knobs: decoupled head_dim (None = hidden/heads), GeGLU
    # MLP act, zero-centered (1+scale) RMSNorm weights, sqrt(d) embedding
    # scaling
    head_dim: Optional[int] = None
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    rms_norm_offset: bool = False
    scale_embeddings: bool = False
    # Gemma-2 knobs: tanh softcapping of attention scores / final logits,
    # sandwich (pre+post) block norms, local/global attention alternating
    # every other layer (even layers use sliding_window, odd layers full
    # causal — HF layer_types convention), and a decoupled attention scale
    # (1/sqrt(query_pre_attn_scalar) instead of 1/sqrt(head_dim))
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    post_block_norms: bool = False
    alternating_sliding_window: bool = False
    query_pre_attn_scalar: Optional[float] = None
    tie_word_embeddings: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "auto"  # see the class docstring
    attention_impl: str = "blockwise"  # "xla" | "blockwise" | "flash"
    attention_kv_block: int = 512
    # flash q-tile rows; v5e-measured: tall q tiles amortize the per-grid-step
    # overhead in the two backward kernels (15% vs 12% of peak at seq 2048)
    attention_block_q: int = 2048
    scan_layers: bool = True
    # MoE (Mixtral-style) — num_experts > 1 replaces the dense MLP with a
    # top-k routed expert FFN (ops/moe.py); a native EP extension over the
    # reference (SURVEY §2.4 EP row)
    num_experts: int = 1
    num_experts_per_tok: int = 2
    expert_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # ST-MoE router z-loss (logit-magnitude regularizer); 0 = off; 1e-3 is
    # the paper default. Lands in the total loss at exactly this weight
    # (per-layer auxes are pre-scaled inside moe_ffn and summed, never
    # re-multiplied)
    router_z_loss_coef: float = 0.0
    # fp8 projections (ops/fp8.py): e4m3 fwd / e5m2 bwd current scaling;
    # set by Accelerator when mixed_precision="fp8"
    use_fp8: bool = False
    # chunked cross-entropy (ops/losses.py): the (B,S,V) logits tensor never
    # materializes — the head matmul is fused into the CE reduction
    use_chunked_ce: bool = False
    ce_chunk_size: int = 4096

    def __post_init__(self):
        # resolved at CONSTRUCTION: when resizing an existing config via
        # dataclasses.replace, pass head_dim=None explicitly (or use the
        # preset factories, which construct fresh) — a stale resolved value
        # cannot be distinguished from a deliberately decoupled one
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.num_experts > 1 and self.hidden_act != "silu":
            raise ValueError(
                "hidden_act is silu-only on the MoE path (ops/moe.py expert "
                f"FFNs); got {self.hidden_act!r} with num_experts="
                f"{self.num_experts}"
            )
        if self.alternating_sliding_window:
            if self.sliding_window is None:
                raise ValueError(
                    "alternating_sliding_window=True needs sliding_window set "
                    "(the even layers' local window size)"
                )
            if self.num_hidden_layers % 2 != 0:
                raise ValueError(
                    "alternating_sliding_window needs an even layer count "
                    "(layers scan as local/global pairs); got "
                    f"{self.num_hidden_layers}"
                )

    def _rope_scaling_key(self):
        """Hashable form for the host-side rope-table cache."""
        if self.rope_scaling is None:
            return None
        return tuple(sorted(self.rope_scaling.items()))

    def serving_family(self):
        """What the serving path asks of a family (models/family.py): every
        layer keeps keys and values, nothing else is kept."""
        from .family import ServingFamily

        return ServingFamily(
            prefill=llama_prefill, prefill_at=llama_prefill_at,
            decode_step=llama_decode_step, verify_step=llama_verify_step,
            kv_layers=self.num_hidden_layers, kv_heads=self.num_key_value_heads,
            head_dim=self.head_dim,
        )

    @classmethod
    def llama2_7b(cls, **overrides) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
        ), **overrides})

    @classmethod
    def mixtral_8x7b(cls, **overrides) -> "LlamaConfig":
        """Mixtral-8x7B shape (HF mistralai/Mixtral-8x7B; block_sparse_moe
        checkpoints convert via :func:`convert_hf_state_dict`)."""
        return cls(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=32768, rope_theta=1e6,
            num_experts=8, num_experts_per_tok=2,
            # dropless (capacity = E): HF Mixtral routes every token to its
            # top-2 unconditionally, so faithful inference must not drop;
            # lower this for capacity-bounded training at scale
            expert_capacity_factor=8.0,
        ), **overrides})

    @classmethod
    def llama3_8b(cls, **overrides) -> "LlamaConfig":
        """Llama-3-8B shape (HF meta-llama/Meta-Llama-3-8B): GQA (8 kv
        heads), 128k vocab, rope_theta=500000."""
        return cls(**{**dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0,
        ), **overrides})

    @classmethod
    def llama3_1_8b(cls, **overrides) -> "LlamaConfig":
        """Llama-3.1-8B shape: llama3_8b + 128k context via llama3-type
        rope scaling."""
        # ride the llama3_8b factory (fresh construction) so overrides like
        # hidden_size re-derive head_dim; dict-merge so max_position/
        # rope_scaling themselves stay overridable like every sibling preset
        return cls.llama3_8b(**{**dict(
            max_position_embeddings=131072,
            rope_scaling={
                "rope_type": "llama3", "factor": 8.0,
                "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                "original_max_position_embeddings": 8192,
            },
        ), **overrides})

    @classmethod
    def qwen2_7b(cls, **overrides) -> "LlamaConfig":
        """Qwen2-7B shape (HF Qwen/Qwen2-7B): llama architecture + GQA (4 kv
        heads) + q/k/v projection BIASES (attention_bias) + tied-free head."""
        return cls(**{**dict(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_hidden_layers=28, num_attention_heads=28, num_key_value_heads=4,
            max_position_embeddings=32768, rope_theta=1e6,
            attention_bias=True, rms_norm_eps=1e-6,
        ), **overrides})

    @classmethod
    def gemma_7b(cls, **overrides) -> "LlamaConfig":
        """Gemma-7B shape (HF google/gemma-7b): decoupled head_dim=256
        (16 heads x 256 = 4096 != hidden 3072), GeGLU MLP, zero-centered
        (1+w) RMSNorm, sqrt(d)-scaled embeddings, tied head."""
        return cls(**{**dict(
            vocab_size=256000, hidden_size=3072, intermediate_size=24576,
            num_hidden_layers=28, num_attention_heads=16, num_key_value_heads=16,
            head_dim=256, max_position_embeddings=8192, rms_norm_eps=1e-6,
            hidden_act="gelu_tanh", rms_norm_offset=True,
            scale_embeddings=True, tie_word_embeddings=True,
        ), **overrides})

    @classmethod
    def gemma2_9b(cls, **overrides) -> "LlamaConfig":
        """Gemma-2-9B shape (HF google/gemma-2-9b): everything Gemma-1 has
        plus attention/final logit softcapping (50/30), sandwich norms
        around both blocks, 4096-token sliding window on every other layer,
        and attention scaled by 1/sqrt(query_pre_attn_scalar=256)."""
        return cls(**{**dict(
            vocab_size=256000, hidden_size=3584, intermediate_size=14336,
            num_hidden_layers=42, num_attention_heads=16, num_key_value_heads=8,
            head_dim=256, max_position_embeddings=8192, rms_norm_eps=1e-6,
            hidden_act="gelu_tanh", rms_norm_offset=True,
            scale_embeddings=True, tie_word_embeddings=True,
            sliding_window=4096, alternating_sliding_window=True,
            attn_logit_softcap=50.0, final_logit_softcap=30.0,
            post_block_norms=True, query_pre_attn_scalar=256.0,
        ), **overrides})

    @classmethod
    def mistral_7b(cls, **overrides) -> "LlamaConfig":
        """Mistral-7B-v0.1 shape (HF mistralai/Mistral-7B-v0.1): llama
        architecture + GQA (8 kv heads) + 4096-token sliding window."""
        return cls(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=32768, rope_theta=10000.0,
            sliding_window=4096,
        ), **overrides})

    @classmethod
    def tiny(cls, **overrides) -> "LlamaConfig":
        """Test-size config."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128,
        ), **overrides})


# ------------------------------------------------------------------- params
def _init_dense(key, in_dim, out_dim, dtype):
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def init_llama_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Stacked-layer parameter pytree."""
    d, i, v = config.hidden_size, config.intermediate_size, config.vocab_size
    h, kvh, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    L = config.num_hidden_layers
    dt = config.param_dtype
    keys = jax.random.split(key, 8)

    def stack_init(k, in_dim, out_dim):
        ks = jax.random.split(k, L)
        return jnp.stack([_init_dense(kk, in_dim, out_dim, dt) for kk in ks])

    if config.num_experts > 1:
        E = config.num_experts
        scale_e = 1.0 / np.sqrt(d)
        mlp = {
            "router": {"kernel": stack_init(keys[5], d, E)},
            "experts": {
                "w_gate": (jax.random.normal(keys[6], (L, E, d, i)) * scale_e).astype(dt),
                "w_up": (jax.random.normal(keys[7], (L, E, d, i)) * scale_e).astype(dt),
                "w_down": (
                    jax.random.normal(jax.random.fold_in(keys[7], 1), (L, E, i, d))
                    * (1.0 / np.sqrt(i))
                ).astype(dt),
            },
        }
    else:
        mlp = {
            "gate_proj": {"kernel": stack_init(keys[5], d, i)},
            "up_proj": {"kernel": stack_init(keys[6], d, i)},
            "down_proj": {"kernel": stack_init(keys[7], i, d)},
        }

    def norm_init(shape):
        # offset convention stores zero-centered weights ((1+w) effective)
        return (jnp.zeros if config.rms_norm_offset else jnp.ones)(shape, dtype=dt)

    def proj(k, in_dim, out_dim, bias):
        entry = {"kernel": stack_init(k, in_dim, out_dim)}
        if bias:
            entry["bias"] = jnp.zeros((L, out_dim), dtype=dt)
        return entry

    ab = config.attention_bias
    params = {
        "embed_tokens": {"embedding": (jax.random.normal(keys[0], (v, d)) * 0.02).astype(dt)},
        "layers": {
            "attn": {
                "q_proj": proj(keys[1], d, h * hd, ab),
                "k_proj": proj(keys[2], d, kvh * hd, ab),
                "v_proj": proj(keys[3], d, kvh * hd, ab),
                "o_proj": {"kernel": stack_init(keys[4], h * hd, d)},
            },
            "mlp": mlp,
            "input_norm": {"scale": norm_init((L, d))},
            "post_attn_norm": {"scale": norm_init((L, d))},
        },
        "final_norm": {"scale": norm_init((d,))},
    }
    if config.post_block_norms:
        # Gemma-2 sandwich norms: block OUTPUTS are normalized before the
        # residual add (attn_out_norm / mlp_out_norm), in addition to the
        # pre-norms (input_norm / post_attn_norm = HF's
        # pre_feedforward_layernorm in this layout)
        params["layers"]["attn_out_norm"] = {"scale": norm_init((L, d))}
        params["layers"]["mlp_out_norm"] = {"scale": norm_init((L, d))}
    if not config.tie_word_embeddings:
        params["lm_head"] = {"kernel": _init_dense(keys[0], d, v, dt)}
    return params


# ------------------------------------------------------------------ forward
def _tanh_softcap(x, cap):
    from ..ops.attention import tanh_softcap

    return tanh_softcap(x, cap)


def _mlp_act(config, gate):
    """SwiGLU's silu or Gemma's GeGLU tanh-gelu on the gate projection."""
    if config.hidden_act == "gelu_tanh":
        return jax.nn.gelu(gate, approximate=True)
    if config.hidden_act != "silu":
        raise ValueError(f"unsupported hidden_act {config.hidden_act!r}")
    return jax.nn.silu(gate)


def rms_norm(x, scale, eps, offset: bool = False):
    """``offset=True``: Gemma convention — stored weights are zero-centered
    and the effective scale is (1 + w)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if offset:
        w = 1.0 + w
    return (y * w).astype(x.dtype)


def _rope_freqs(head_dim: int, theta: float, scaling=None) -> np.ndarray:
    """Base inverse frequencies, optionally rope-scaled. ``scaling`` is the
    hashable ``LlamaConfig._rope_scaling_key()`` tuple (or None)."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    if scaling is None:
        return freqs
    cfg = dict(scaling)
    rope_type = cfg.get("rope_type", cfg.get("type"))
    if rope_type is None:
        raise ValueError(
            "rope_scaling needs an explicit 'rope_type' ('linear' or "
            "'llama3') — defaulting silently would apply the wrong geometry"
        )
    factor = float(cfg.get("factor", 1.0))
    if rope_type == "linear":
        # position/f is the same angle as freq/f (reference linear scaling)
        return freqs / factor
    if rope_type == "llama3":
        # HF Llama-3.1: long wavelengths scale by 1/f, short ones keep the
        # pretrained geometry, mid-band interpolates smoothly
        low = float(cfg.get("low_freq_factor", 1.0))
        high = float(cfg.get("high_freq_factor", 4.0))
        orig = float(cfg.get("original_max_position_embeddings", 8192))
        wavelen = 2 * np.pi / freqs
        smooth = (orig / wavelen - low) / (high - low)
        smooth = np.clip(smooth, 0.0, 1.0)
        return (1 - smooth) * freqs / factor + smooth * freqs
    raise ValueError(f"unsupported rope_scaling type {rope_type!r} "
                     "(supported: linear, llama3)")


@functools.lru_cache(maxsize=8)
def _rope_tables(seq_len: int, head_dim: int, theta: float, scaling=None):
    # host-side cache (numpy) — jnp conversion happens per-trace so no tracers
    # leak into the cache
    pos = np.arange(seq_len)
    freqs = _rope_freqs(head_dim, theta, scaling)
    angles = np.outer(pos, freqs)  # (S, hd/2)
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def apply_rope(x: jax.Array, position_offset: int, theta: float,
               position_ids=None, scaling=None) -> jax.Array:
    """Rotary embedding on (B, S, H, D); ``position_offset`` supports CP/SP
    shards that start mid-sequence. ``position_ids`` (B, S) overrides with
    per-token positions (packed rows restart at each document —
    utils/native.packed_position_ids). ``scaling``: rope-scaling key
    (LlamaConfig._rope_scaling_key)."""
    b, s, h, d = x.shape
    cos_np, sin_np = _rope_tables(s + position_offset, d, theta, scaling)
    if position_ids is not None:
        cos = jnp.asarray(cos_np)[position_ids][:, :, None, :]  # (B, S, 1, hd/2)
        sin = jnp.asarray(sin_np)[position_ids][:, :, None, :]
    else:
        cos = jnp.asarray(cos_np[position_offset : position_offset + s])[None, :, None, :]
        sin = jnp.asarray(sin_np[position_offset : position_offset + s])[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(b, s, h, d)
    return out.astype(x.dtype)


# ``remat_policy`` by name -> the ``policy`` of ``jax.checkpoint`` ("full" is
# no checkpoint at all and has no entry)
_REMAT_POLICIES = {
    "nothing": None,
    # save only the two per-layer block outputs (tagged in _layer):
    # ~2 activations/layer instead of 7 under "dots", at the cost of
    # recomputing qkv/gate/up projections in backward (~40% of fwd FLOPs
    # vs 100% for "nothing")
    "minimal": jax.checkpoint_policies.save_only_these_names(
        "attn_block_out", "mlp_block_out"
    ),
    # matmul outputs, and what the flash kernel hands its backward kernels
    # (ops/flash_attention.py names them): the kernel is attention's two
    # matmuls, and without its results a layer's backward runs it again
    "dots": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots,
        jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse"),
    ),
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}

# What ``"auto"`` may become, fastest first: ``train_step`` keeps the first
# whose compiled step fits. The order is a step's time on the chip, which was
# also the order of the bytes a rung holds (TPU v5e, Mistral-7B widths, 2,048
# tokens: dots 88.0 ms, full 92.1, minimal 95.3, nothing 96.6; PERF.md
# section 6, PR 36): saving every intermediate writes and reads back more
# than recomputing the cheap ones between the matmuls costs.
REMAT_LADDER = ("dots", "full", "minimal", "nothing")

_auto_remat = contextvars.ContextVar("accelerate_tpu_auto_remat", default="nothing")


@contextlib.contextmanager
def auto_remat(rung: str):
    """What ``remat_policy="auto"`` means to a program traced inside: the
    train step's plan sets its rung here, around the trace."""
    token = _auto_remat.set(rung)
    try:
        yield
    finally:
        _auto_remat.reset(token)


def checkpoint_layer(config, layer_fn):
    """``layer_fn`` under the configuration's ``remat_policy``: the one place a
    layer loop (llama's and GPT-2's, scanned, paired or a pipeline's stage)
    reads it."""
    name = config.remat_policy
    if name == "auto":
        name = _auto_remat.get()
    if name == "full":
        return layer_fn
    if name not in _REMAT_POLICIES:
        raise ValueError(
            f"remat_policy={config.remat_policy!r}: expected \"auto\", \"full\" or "
            f"one of {sorted(_REMAT_POLICIES)}"
        )
    return jax.checkpoint(layer_fn, policy=_REMAT_POLICIES[name])


def _dot(config: LlamaConfig, x, w, tp_dim=None):
    """Projection matmul, optionally via the fp8 path. ``w`` arrives already
    cast to the compute dtype; ``gather_over_fsdp`` pins its use-time layout
    (bf16 all-gather, tp axis kept on ``tp_dim``)."""
    w = gather_over_fsdp(w, tp_dim=tp_dim)
    if config.use_fp8:
        from ..ops.fp8 import fp8_dot

        return fp8_dot(x, w)
    return x @ w


def _attention(config: LlamaConfig, q, k, v, attention_fn=None, q_offset: int = 0,
               segment_ids=None, window="config"):
    if window == "config":
        window = config.sliding_window
    if attention_fn is not None:
        extra_kw = {}
        if window != getattr(attention_fn, "window", None):
            # a window-aware ring/Ulysses fn carries its build-time window
            # as .window; fns built by this framework additionally accept a
            # per-call STATIC window override (Gemma-2's local/global
            # alternation — each distinct window traces its own branch)
            if getattr(attention_fn, "supports_window_override", False):
                extra_kw["window"] = window
            else:
                raise ValueError(
                    "sliding_window cannot compose with this mesh-injected "
                    f"attention_fn (built for window="
                    f"{getattr(attention_fn, 'window', None)}, layer wants "
                    f"{window}) and the fn accepts no per-call window "
                    "override; the Accelerator-built CP/SP attention fns do"
                )
        if config.attn_logit_softcap != getattr(attention_fn, "softcap", None):
            # ring/Ulysses fns carry their build-time cap as .softcap
            # (ops/ring_attention.py, ops/ulysses.py) — a mismatch would
            # silently attend with the wrong (or no) capping
            raise ValueError(
                "attn_logit_softcap mismatch with the mesh-injected "
                f"attention_fn (built for softcap="
                f"{getattr(attention_fn, 'softcap', None)}, layer wants "
                f"{config.attn_logit_softcap}): the Accelerator builds "
                "capped CP/SP attention from the model config automatically"
            )
        if segment_ids is not None:
            # packed sequences under CP/SP: document labels shard with the
            # sequence (ring rotates kv labels; Ulysses all-gathers them)
            return attention_fn(
                q, k, v, causal=True, segment_ids=segment_ids, **extra_kw
            )
        return attention_fn(q, k, v, causal=True, **extra_kw)
    from ..ops.attention import dispatch_attention

    return dispatch_attention(
        config.attention_impl, q, k, v, causal=True, q_offset=q_offset,
        kv_block=config.attention_kv_block, block_q=config.attention_block_q,
        segment_ids=segment_ids, window=window,
        softcap=config.attn_logit_softcap,
    )


def _layer(
    config: LlamaConfig,
    layer_params,
    x,
    position_offset: int,
    attention_fn,
    collect_kv: bool = False,
    segment_ids=None,
    position_ids=None,
    window="config",
):
    """One transformer block on (B, S, D) activations. ``collect_kv=True``
    additionally returns the (post-RoPE) k/v for prefill cache building.
    ``window`` overrides ``config.sliding_window`` for this layer (Gemma-2
    alternates local/global layers)."""
    h, kvh, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    b, s, d = x.shape
    cdt = config.compute_dtype

    residual = x
    y = rms_norm(x, layer_params["input_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)

    def _proj(name):
        p = layer_params["attn"][name]
        out = _dot(config, y, p["kernel"].astype(cdt), tp_dim=1)  # column
        if "bias" in p:  # Qwen2-style q/k/v biases (config.attention_bias)
            out = out + p["bias"].astype(cdt)
        return out

    q = _proj("q_proj").reshape(b, s, h, hd)
    k = _proj("k_proj").reshape(b, s, kvh, hd)
    v = _proj("v_proj").reshape(b, s, kvh, hd)
    _sc = config._rope_scaling_key()
    q = apply_rope(q, position_offset, config.rope_theta, position_ids, _sc)
    k = apply_rope(k, position_offset, config.rope_theta, position_ids, _sc)
    # Megatron-SP transition: full sequence, heads over tp (see
    # constrain_activation kind="heads")
    q = constrain_activation(q, "heads")
    k = constrain_activation(k, "heads")
    v = constrain_activation(v, "heads")
    kv_out = (k, v) if collect_kv else None
    if config.query_pre_attn_scalar is not None:
        # every attention impl scales by 1/sqrt(head_dim); pre-multiplying q
        # by sqrt(hd / qpas) makes the effective scale 1/sqrt(qpas) without
        # plumbing a scale through the kernels (Gemma-2)
        q = q * jnp.asarray(
            math.sqrt(hd / config.query_pre_attn_scalar), dtype=q.dtype
        )
    attn = _attention(
        config, q, k, v, attention_fn, q_offset=position_offset,
        segment_ids=segment_ids, window=window,
    )
    attn = _dot(config, attn.reshape(b, s, h * hd),
                layer_params["attn"]["o_proj"]["kernel"].astype(cdt), tp_dim=0)
    if config.post_block_norms:  # Gemma-2 sandwich: normalize the block OUT
        attn = rms_norm(attn, layer_params["attn_out_norm"]["scale"],
                        config.rms_norm_eps, config.rms_norm_offset)
    attn = checkpoint_name(attn, "attn_block_out")
    x = constrain_activation(residual + attn)

    residual = x
    y = rms_norm(x, layer_params["post_attn_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)
    if config.num_experts > 1:
        from ..ops.moe import moe_ffn

        y, aux = moe_ffn(
            y,
            layer_params["mlp"]["router"]["kernel"],
            layer_params["mlp"]["experts"]["w_gate"],
            layer_params["mlp"]["experts"]["w_up"],
            layer_params["mlp"]["experts"]["w_down"],
            num_selected=config.num_experts_per_tok,
            capacity_factor=config.expert_capacity_factor,
            compute_dtype=cdt,
            aux_loss_coef=config.moe_aux_loss_coef,
            router_z_loss_coef=config.router_z_loss_coef,
        )
    else:
        gate = _dot(config, y, layer_params["mlp"]["gate_proj"]["kernel"].astype(cdt), tp_dim=1)
        up = _dot(config, y, layer_params["mlp"]["up_proj"]["kernel"].astype(cdt), tp_dim=1)
        y = constrain_activation(_mlp_act(config, gate) * up, "intermediate")
        y = _dot(config, y, layer_params["mlp"]["down_proj"]["kernel"].astype(cdt), tp_dim=0)
        aux = jnp.float32(0.0)
    if config.post_block_norms:
        y = rms_norm(y, layer_params["mlp_out_norm"]["scale"],
                     config.rms_norm_eps, config.rms_norm_offset)
    y = checkpoint_name(y, "mlp_block_out")
    out = constrain_activation(residual + y)
    if collect_kv:
        return out, aux, kv_out
    return out, aux


def _alternating_fns(config: LlamaConfig, layer_kw: dict, remat: bool = True):
    """(local_fn, global_fn) layer variants for Gemma-2's local/global
    alternation — built ONCE so both windows stay static in their compiled
    bodies (the flash kernel's window tile-pruning needs a static window)."""
    local_fn = functools.partial(
        _layer, config, window=config.sliding_window, **layer_kw
    )
    global_fn = functools.partial(_layer, config, window=None, **layer_kw)
    if remat:
        local_fn = checkpoint_layer(config, local_fn)
        global_fn = checkpoint_layer(config, global_fn)
    return local_fn, global_fn


def _make_pair_fn(local_fn, global_fn, keep_aux: bool = True):
    """One local+global pair body — the single source for every
    alternating-scan site (stack/pipeline/stage/prefill)."""

    def pair_fn(pair_params, h):
        lp0, lp1 = _pair_slices(pair_params)
        h, a0 = local_fn(lp0, h)
        h, a1 = global_fn(lp1, h)
        return h, (a0 + a1 if keep_aux else None)

    return pair_fn


def _pair_layers(params_layers):
    """Stacked (L, ...) leaves → (L/2, 2, ...) for the pair scan."""
    return jax.tree_util.tree_map(
        lambda p: p.reshape(p.shape[0] // 2, 2, *p.shape[1:]), params_layers
    )


def _pair_slices(pair_params):
    lp0 = jax.tree_util.tree_map(lambda p: p[0], pair_params)
    lp1 = jax.tree_util.tree_map(lambda p: p[1], pair_params)
    return lp0, lp1


def llama_apply(
    config: LlamaConfig,
    params: dict,
    input_ids: jax.Array,
    position_offset: int = 0,
    attention_fn: Optional[Callable] = None,
    layer_stack_fn: Optional[Callable] = None,
    return_aux: bool = False,
    segment_ids: Optional[jax.Array] = None,
    position_ids: Optional[jax.Array] = None,
):
    """Forward: (B, S) int tokens → (B, S, V) float32 logits.

    ``segment_ids`` (B, S) int32: packed-sequence document labels — attention
    never crosses a boundary (ops/flash_attention segment masking; llama_loss
    forwards ``batch["segment_ids"]`` automatically). ``position_ids``
    (B, S) int32: per-token RoPE positions (restart at packed-document
    starts — utils/native.packed_position_ids).

    ``return_aux=True`` additionally returns {"aux_loss": scalar} (MoE
    load-balancing loss summed over layers). ``layer_stack_fn`` overrides how
    the stacked layers run (injected by pipeline parallelism)."""
    cdt = config.compute_dtype
    # explicit use-time all-gather of the (possibly fsdp/tp-sharded) table:
    # a gather from a sharded table is the partitioner's worst case (it
    # replicates involuntarily); same bytes moved, no pathological reshard
    # cast BEFORE the gather: the replication then moves bf16, not f32
    table = replicate_over_fsdp(
        params["embed_tokens"]["embedding"].astype(cdt), keep_tp=False
    )
    x = table[input_ids]
    if config.scale_embeddings:  # Gemma: sqrt(d) in the embedding path
        x = x * jnp.asarray(config.hidden_size**0.5, dtype=cdt)
    x = constrain_activation(x)

    layer_kw = dict(
        position_offset=position_offset, attention_fn=attention_fn,
        segment_ids=segment_ids, position_ids=position_ids,
    )
    layer_fn = checkpoint_layer(config, functools.partial(_layer, config, **layer_kw))

    alternating = config.alternating_sliding_window
    if layer_stack_fn is not None:
        if alternating:
            # the pipeline scans layer PAIRS as its stack unit, so every
            # stage holds whole local/global pairs and both windows stay
            # static inside the compiled stage body (_alternating_fns)
            local_fn, global_fn = _alternating_fns(config, layer_kw)
            pair_fn = _make_pair_fn(local_fn, global_fn)
            x, aux_raw = layer_stack_fn(
                _pair_layers(params["layers"]), x, pair_fn
            )
        else:
            x, aux_raw = layer_stack_fn(params["layers"], x, layer_fn)
        aux_total = aux_raw  # per-layer auxes are pre-scaled (moe_ffn)
    elif alternating and config.scan_layers:
        # local/global layers alternate: scan over layer PAIRS (see
        # _alternating_fns for why both windows must stay static)
        local_fn, global_fn = _alternating_fns(config, layer_kw)
        pair_fn = _make_pair_fn(local_fn, global_fn)

        def pair_body(x, pair_params):
            return pair_fn(pair_params, x)

        x, aux_per_pair = lax.scan(pair_body, x, _pair_layers(params["layers"]))
        aux_total = jnp.sum(aux_per_pair)
    elif config.scan_layers:
        def scan_body(x, layer_params):
            x, aux = layer_fn(layer_params, x)
            return x, aux

        x, aux_per_layer = lax.scan(scan_body, x, params["layers"])
        aux_total = jnp.sum(aux_per_layer)  # pre-scaled per layer
    else:
        L = config.num_hidden_layers
        aux_total = jnp.float32(0.0)
        if alternating:
            local_fn, global_fn = _alternating_fns(config, layer_kw)
        for li in range(L):
            lp = jax.tree_util.tree_map(lambda p: p[li], params["layers"])
            if alternating:
                fn = local_fn if li % 2 == 0 else global_fn
                x, aux = fn(lp, x)
            else:
                x, aux = layer_fn(lp, x)
            aux_total = aux_total + aux
        # aux_total already pre-scaled per layer

    x = rms_norm(x, params["final_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)
    head = (
        params["embed_tokens"]["embedding"].T
        if config.tie_word_embeddings
        else params["lm_head"]["kernel"]
    )
    if config.use_chunked_ce:
        # hand the pre-head hidden + head kernel to the fused CE path
        # (training-only mode: llama_loss consumes this; use the decode path
        # or use_chunked_ce=False for inference logits)
        out = {"hidden": x, "head_kernel": head,
               "logit_softcap": config.final_logit_softcap}
        if return_aux:
            out["aux_loss"] = aux_total
        return out
    # use-time all-gather of the fsdp-sharded head; keeps logits (and their
    # cotangents) on the batch/seq layout — see replicate_over_fsdp
    logits = jnp.einsum(
        "bsd,dv->bsv", x, replicate_over_fsdp(head.astype(cdt)),
        preferred_element_type=jnp.float32,  # G402: f32 logit accumulation
    )
    logits = _tanh_softcap(logits, config.final_logit_softcap)  # Gemma-2
    logits = constrain_activation(logits, "vocab")
    if return_aux:
        return logits, {"aux_loss": aux_total}
    return logits


def _mask_of(labels, mask):
    """HF semantics: explicit loss_mask wins (sliced to the label length),
    else labels < 0 (the -100 ignore index) are excluded."""
    if mask is None:
        return (labels >= 0).astype(jnp.float32)
    return mask[:, : labels.shape[1]].astype(jnp.float32)


def _dense_ce_from_logits(logits, labels, mask, reduction="mean"):
    """Masked CE from full logits. One-hot einsum instead of
    take_along_axis: its transpose is a clean matmul where the gather's
    backward is a scatter-add the SPMD partitioner reshards involuntarily
    under dp×cp meshes. ``reduction="sum"`` returns the masked nll SUM —
    the caller divides by its own (global) valid-token count."""
    mask = _mask_of(labels, mask)
    labels = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    # one-hot in the logits dtype — a float32 copy would double the (B,S,V)
    # transient; the f32 accumulation happens inside the einsum
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.einsum(
        "bsv,bsv->bs", logits, onehot, preferred_element_type=jnp.float32
    )
    total = jnp.sum((lse - label_logit) * mask)
    if reduction == "sum":
        return total
    return total / jnp.maximum(jnp.sum(mask), 1)


def _ce_from_hidden(config, x, head, labels, mask, *, reduction="mean",
                    ce_chunk_size=None):
    """Shared CE tail (label mask/-100 handling, chunked or dense) used by
    both :func:`llama_loss` and the 1F1B pipeline head so the two paths stay
    provably identical."""
    if config.use_chunked_ce:
        from ..ops.losses import chunked_softmax_cross_entropy

        return chunked_softmax_cross_entropy(
            x, head.astype(x.dtype), jnp.maximum(labels, 0),
            chunk_size=ce_chunk_size or config.ce_chunk_size,
            loss_mask=_mask_of(labels, mask), reduction=reduction,
            # getattr: this CE tail is shared with families whose configs
            # predate the Gemma-2 field (gpt2's 1F1B head)
            logit_softcap=getattr(config, "final_logit_softcap", None),
        )
    # all-gather the fsdp-sharded head for the logits matmul (the standard
    # FSDP use-time gather). Without this the partitioner keeps logits
    # vocab-sharded to match the head while the CE math runs
    # batch/seq-sharded, and the backward transpose hits the involuntary
    # full-rematerialization path (d_logits {batch,seq} -> {vocab} flip).
    # With a replicated head, d_head is a local partial + psum — clean.
    head = replicate_over_fsdp(head.astype(config.compute_dtype))
    logits = jnp.einsum(
        "bsd,dv->bsv", x, head,
        preferred_element_type=jnp.float32,  # G402: f32 logit accumulation
    )
    logits = _tanh_softcap(logits, getattr(config, "final_logit_softcap", None))
    logits = constrain_activation(logits, "vocab")
    return _dense_ce_from_logits(logits, labels, mask, reduction=reduction)


def llama_ce_denominator(batch):
    """Global valid-token count matching :func:`_ce_from_hidden`'s mask —
    the denominator the 1F1B schedule divides its per-microbatch nll sums
    by (so cross-microbatch mask imbalance keeps llama_loss semantics)."""
    labels = batch.get("labels")
    if labels is None:
        labels = batch["input_ids"][:, 1:]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = (labels >= 0).astype(jnp.float32)
    else:
        mask = mask[:, : labels.shape[1]].astype(jnp.float32)
    return jnp.maximum(jnp.sum(mask), 1)


def llama_loss(model_view, batch, ce_chunk_size: int = 4096):
    """Next-token cross entropy; ``batch = {"input_ids": (B,S)}`` with
    optional ``"labels"`` (defaults to shifted input_ids), ``"loss_mask"``,
    and ``"segment_ids"`` (packed-sequence document labels — forwarded to
    the model so attention never crosses a document boundary). MoE models
    fold the load-balancing aux loss in. With ``config.use_chunked_ce`` the
    head matmul fuses into the CE reduction (ops/losses.py) and full logits
    never materialize (``ce_chunk_size`` vocab slices; static)."""
    input_ids = batch["input_ids"]
    packed_kwargs = {
        kk: batch[kk] for kk in ("segment_ids", "position_ids") if kk in batch
    }
    out = model_view(input_ids, **packed_kwargs)
    labels = batch.get("labels")
    mask = batch.get("loss_mask")
    if isinstance(out, dict) and "hidden" in out:
        from ..ops.losses import chunked_softmax_cross_entropy

        hidden = out["hidden"]
        if labels is None:
            labels = input_ids[:, 1:]
            hidden = hidden[:, :-1]
        loss = chunked_softmax_cross_entropy(
            hidden,
            out["head_kernel"].astype(hidden.dtype),
            jnp.maximum(labels, 0),
            chunk_size=ce_chunk_size,
            loss_mask=_mask_of(labels, mask),
            # Gemma-2: the protocol dict carries the final-logit cap so the
            # fused CE trains against the SAME capped logits inference serves
            logit_softcap=out.get("logit_softcap"),
        )
        if "aux_loss" in out:
            loss = loss + out["aux_loss"]
        return loss
    if isinstance(out, tuple):
        logits, aux = out
    else:
        logits, aux = out, None
    if labels is None:
        labels = input_ids[:, 1:]
        logits = logits[:, :-1]
    loss = _dense_ce_from_logits(logits, labels, mask)
    if aux is not None:
        loss = loss + aux["aux_loss"]
    return loss


def llama_pipeline_parts(config: LlamaConfig, attention_fn: Optional[Callable] = None):
    """(embed_fn, stage_fn, head_loss_fn) for the hand-scheduled 1F1B
    pipeline (parallel/pp_1f1b.py). The head loss mirrors :func:`llama_loss`
    (label shift, loss_mask, HF -100 ignore index, chunked CE).

    MoE aux losses are not yet folded into the 1F1B path — Accelerator falls
    back to GPipe for expert models."""
    cdt = config.compute_dtype
    layer_fn = checkpoint_layer(config, functools.partial(
        _layer, config, position_offset=0, attention_fn=attention_fn
    ))
    alt_fns = None
    if config.alternating_sliding_window:
        # stage slices start on even global layer indices whenever the
        # rows-per-stage count is even (enforced below), so pairing within
        # the slice preserves the global local/global alternation
        alt_fns = _alternating_fns(
            config,
            dict(position_offset=0, attention_fn=attention_fn),
        )

    def embed_fn(params, mb):
        x = params["embed_tokens"]["embedding"].astype(cdt)[mb["input_ids"]]
        if config.scale_embeddings:  # Gemma: sqrt(d) in the embedding path
            x = x * jnp.asarray(config.hidden_size**0.5, dtype=cdt)
        return constrain_activation(x)

    def stage_fn(stage_params, h):
        if alt_fns is not None:
            rows = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
            if rows % 2:
                raise ValueError(
                    "alternating_sliding_window under pp needs an even "
                    f"layer count per stage/chunk; got {rows} — choose "
                    "pp (and virtual stages) so layers/(pp*v) is even"
                )
            pair_fn = _make_pair_fn(*alt_fns, keep_aux=False)

            def pair_body(h, pair_params):
                return pair_fn(pair_params, h)

            h, _ = lax.scan(pair_body, h, _pair_layers(stage_params))
            return h

        def body(h, lp):
            h, _aux = layer_fn(lp, h)
            return h, None

        h, _ = lax.scan(body, h, stage_params)
        return h

    def head_loss_fn(params, h, mb):
        """Masked nll SUM over this microbatch (reduction handled by the
        schedule: it divides by the GLOBAL valid-token count from
        :func:`llama_ce_denominator`, so per-microbatch mask imbalance keeps
        exactly llama_loss's sum/count semantics)."""
        x = rms_norm(h, params["final_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)
        head = (
            params["embed_tokens"]["embedding"].T
            if config.tie_word_embeddings
            else params["lm_head"]["kernel"]
        )
        labels = mb.get("labels")
        mask = mb.get("loss_mask")
        if labels is None:
            labels = mb["input_ids"][:, 1:]
            x = x[:, :-1]
        return _ce_from_hidden(config, x, head, labels, mask, reduction="sum")

    return embed_fn, stage_fn, head_loss_fn, llama_ce_denominator


# --------------------------------------------------------- HF checkpoint IO
def _rope_unpermute(w: np.ndarray, n_heads: int, head_dim: int) -> np.ndarray:
    """HF rotate-half convention → our interleaved RoPE convention.

    HF checkpoints store q/k so that rotary pairs head-dim rows (i, i+d/2)
    ("rotate half"); our apply_rope pairs (2i, 2i+1) (the original Meta
    interleaved/complex form). This is the inverse of the permute() in
    transformers' convert_llama_weights_to_hf: for torch-layout (out, in),
    ours[h, 2i+m] = hf[h, m*d/2 + i].
    """
    out_dim, in_dim = w.shape
    half = head_dim // 2
    v = w.reshape(n_heads, 2, half, in_dim)  # (h, member m, pair i, in)
    v = v.transpose(0, 2, 1, 3)  # (h, pair i, member m, in)
    return v.reshape(out_dim, in_dim)


def _rope_permute(w: np.ndarray, n_heads: int, head_dim: int) -> np.ndarray:
    """Inverse of :func:`_rope_unpermute` (ours → HF) for export."""
    out_dim, in_dim = w.shape
    half = head_dim // 2
    v = w.reshape(n_heads, half, 2, in_dim)  # (h, pair i, member m, in)
    v = v.transpose(0, 2, 1, 3)  # (h, member m, pair i, in)
    return v.reshape(out_dim, in_dim)


_HF_LAYER_MAP = {
    "self_attn.q_proj.weight": ("attn", "q_proj"),
    "self_attn.k_proj.weight": ("attn", "k_proj"),
    "self_attn.v_proj.weight": ("attn", "v_proj"),
    "self_attn.o_proj.weight": ("attn", "o_proj"),
    "mlp.gate_proj.weight": ("mlp", "gate_proj"),
    "mlp.up_proj.weight": ("mlp", "up_proj"),
    "mlp.down_proj.weight": ("mlp", "down_proj"),
}


def convert_hf_state_dict(config: LlamaConfig, flat: dict) -> dict:
    """Convert a HuggingFace Llama checkpoint (flat torch-naming dict of
    arrays, e.g. from safetensors) into our stacked-scan pytree.

    The two representational gaps (SURVEY §7 "checkpoint compatibility"):
    torch ``nn.Linear`` stores (out, in) → transposed to flax (in, out); and
    per-layer tensors ``model.layers.{i}.*`` are stacked on a leading L dim.
    """
    L = config.num_hidden_layers
    get = lambda k: np.asarray(flat[k])

    def stacked(suffix: str, transpose: bool) -> jnp.ndarray:
        parts = []
        rope_heads = None
        if suffix.startswith("self_attn.q_proj"):
            rope_heads = config.num_attention_heads
        elif suffix.startswith("self_attn.k_proj"):
            rope_heads = config.num_key_value_heads
        for i in range(L):
            w = get(f"model.layers.{i}.{suffix}")
            if rope_heads is not None:
                w = _rope_unpermute(w, rope_heads, config.head_dim)
            parts.append(w.T if transpose else w)
        return jnp.asarray(np.stack(parts), dtype=config.param_dtype)

    params = {
        "embed_tokens": {
            "embedding": jnp.asarray(get("model.embed_tokens.weight"), dtype=config.param_dtype)
        },
        "layers": {
            "attn": {},
            "mlp": {},
            "input_norm": {"scale": stacked("input_layernorm.weight", transpose=False)},
        },
        "final_norm": {"scale": jnp.asarray(get("model.norm.weight"), dtype=config.param_dtype)},
    }
    if config.post_block_norms:
        # Gemma-2 sandwich norms: HF's post_attention_layernorm normalizes
        # the attention OUTPUT (our attn_out_norm) and pre_feedforward_
        # layernorm is the pre-MLP norm (our post_attn_norm slot)
        params["layers"]["attn_out_norm"] = {
            "scale": stacked("post_attention_layernorm.weight", transpose=False)
        }
        params["layers"]["post_attn_norm"] = {
            "scale": stacked("pre_feedforward_layernorm.weight", transpose=False)
        }
        params["layers"]["mlp_out_norm"] = {
            "scale": stacked("post_feedforward_layernorm.weight", transpose=False)
        }
    else:
        params["layers"]["post_attn_norm"] = {
            "scale": stacked("post_attention_layernorm.weight", transpose=False)
        }
    if config.num_experts > 1:
        # HF Mixtral layout: block_sparse_moe.gate (router, torch (E, D)) and
        # experts.{e}.{w1,w3,w2} (gate/up/down, torch (out, in)); ours stacks
        # layers on dim 0 and experts on dim 1
        E = config.num_experts

        def stacked_experts(w_name: str) -> jnp.ndarray:
            per_layer = []
            for i in range(L):
                per_layer.append(np.stack([
                    get(f"model.layers.{i}.block_sparse_moe.experts.{e}.{w_name}.weight").T
                    for e in range(E)
                ]))
            return jnp.asarray(np.stack(per_layer), dtype=config.param_dtype)

        params["layers"]["mlp"] = {
            "router": {"kernel": stacked("block_sparse_moe.gate.weight", transpose=True)},
            "experts": {
                "w_gate": stacked_experts("w1"),
                "w_up": stacked_experts("w3"),
                "w_down": stacked_experts("w2"),
            },
        }
        layer_map = {k: v for k, v in _HF_LAYER_MAP.items() if v[0] == "attn"}
    else:
        layer_map = _HF_LAYER_MAP
    for hf_suffix, (group, name) in layer_map.items():
        params["layers"][group][name] = {"kernel": stacked(hf_suffix, transpose=True)}
    if not config.attention_bias and "model.layers.0.self_attn.q_proj.bias" in flat:
        raise ValueError(
            "checkpoint carries q/k/v projection biases (Qwen2-style) but "
            "config.attention_bias=False — they would be silently dropped "
            "and every logit would diverge from HF; set attention_bias=True "
            "(see LlamaConfig.qwen2_7b)"
        )
    if config.attention_bias:
        # Qwen2-style q/k/v biases; q/k biases live in the same rotate-half
        # row layout as the kernels, so the same unpermute applies (as a
        # 1-column matrix)
        for name, heads in (("q_proj", config.num_attention_heads),
                            ("k_proj", config.num_key_value_heads),
                            ("v_proj", None)):
            rows = []
            for i in range(L):
                bvec = np.asarray(flat[f"model.layers.{i}.self_attn.{name}.bias"])
                if heads is not None:
                    bvec = _rope_unpermute(bvec[:, None], heads, config.head_dim)[:, 0]
                rows.append(bvec)
            params["layers"]["attn"][name]["bias"] = jnp.asarray(
                np.stack(rows), dtype=config.param_dtype
            )
    if not config.tie_word_embeddings:
        if "lm_head.weight" in flat:
            params["lm_head"] = {
                "kernel": jnp.asarray(get("lm_head.weight").T, dtype=config.param_dtype)
            }
        else:  # tied checkpoint loaded into untied config
            params["lm_head"] = {
                "kernel": jnp.asarray(get("model.embed_tokens.weight").T, dtype=config.param_dtype)
            }
    return params


def export_hf_state_dict(config: LlamaConfig, params: dict) -> dict:
    """Inverse of :func:`convert_hf_state_dict` (for torch-ecosystem export)."""
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed_tokens"]["embedding"]),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    L = config.num_hidden_layers
    if config.num_experts > 1:
        layer_map = {k: v for k, v in _HF_LAYER_MAP.items() if v[0] == "attn"}
        router = np.asarray(params["layers"]["mlp"]["router"]["kernel"])
        experts = params["layers"]["mlp"]["experts"]
        for i in range(L):
            out[f"model.layers.{i}.block_sparse_moe.gate.weight"] = router[i].T
            for e in range(config.num_experts):
                for ours, hf_w in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
                    out[
                        f"model.layers.{i}.block_sparse_moe.experts.{e}.{hf_w}.weight"
                    ] = np.asarray(experts[ours])[i, e].T
    else:
        layer_map = _HF_LAYER_MAP
    for hf_suffix, (group, name) in layer_map.items():
        stacked = np.asarray(params["layers"][group][name]["kernel"])
        rope_heads = None
        if name == "q_proj":
            rope_heads = config.num_attention_heads
        elif name == "k_proj":
            rope_heads = config.num_key_value_heads
        bias = params["layers"][group][name].get("bias")
        for i in range(L):
            w = stacked[i].T  # → torch layout (out, in)
            if rope_heads is not None:
                w = _rope_permute(w, rope_heads, config.head_dim)
            out[f"model.layers.{i}.{hf_suffix}"] = w
            if bias is not None:
                bvec = np.asarray(bias)[i]
                if rope_heads is not None:
                    bvec = _rope_permute(bvec[:, None], rope_heads, config.head_dim)[:, 0]
                out[f"model.layers.{i}.{hf_suffix[:-len('.weight')]}.bias"] = bvec
    for i in range(L):
        out[f"model.layers.{i}.input_layernorm.weight"] = np.asarray(
            params["layers"]["input_norm"]["scale"]
        )[i]
        if config.post_block_norms:  # Gemma-2 four-norm mapping (see import)
            out[f"model.layers.{i}.post_attention_layernorm.weight"] = np.asarray(
                params["layers"]["attn_out_norm"]["scale"]
            )[i]
            out[f"model.layers.{i}.pre_feedforward_layernorm.weight"] = np.asarray(
                params["layers"]["post_attn_norm"]["scale"]
            )[i]
            out[f"model.layers.{i}.post_feedforward_layernorm.weight"] = np.asarray(
                params["layers"]["mlp_out_norm"]["scale"]
            )[i]
        else:
            out[f"model.layers.{i}.post_attention_layernorm.weight"] = np.asarray(
                params["layers"]["post_attn_norm"]["scale"]
            )[i]
    if "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["kernel"]).T
    return out


def load_hf_checkpoint(model: Model, directory: str) -> None:
    """Load a HuggingFace-format safetensors Llama checkpoint into ``model``,
    honoring its current shardings (streams shard-by-shard)."""
    from ..utils.serialization import load_sharded_safetensors

    flat = load_sharded_safetensors(directory)
    params = convert_hf_state_dict(model.config, flat)
    model.load_state_dict(params)


# ----------------------------------------------------------------- decoding
def init_kv_cache(config: LlamaConfig, batch_size: int, max_len: int, dtype=None):
    """Per-layer stacked KV cache (L, B, max_len, Hkv, hd)."""
    dtype = dtype or config.compute_dtype
    shape = (
        config.num_hidden_layers,
        batch_size,
        max_len,
        config.num_key_value_heads,
        config.head_dim,
    )
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}


def _step_block(config: LlamaConfig, layer_params, x, pos, attend):
    """One block over a window of W new positions a row: ``x`` (B, W, D) at
    positions ``pos .. pos+W-1``. W = 1 is a decode step (``pos`` a traced
    scalar, the whole batch in lockstep as in the fused generate scan, or a
    traced (B,) vector, each continuous-batching slot at its own position);
    W > 1 is a speculative-verify or prefill-chunk window (``pos`` (B,)).

    ``attend(q, k, v) -> (attn, kept)`` is the block's only contact with the
    cache: :func:`~accelerate_tpu.kvcache.attend_step` or ``attend_window``
    bound to the store, the layer and this config's attention arguments,
    handed the rope-rotated projections. It owns the write, the choice of
    attend path and the attention; ``kept`` (the updated store, or the
    window's keys and values) is returned beside the new ``x``."""
    h, kvh, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    b, w, d = x.shape
    cdt = config.compute_dtype

    residual = x
    y = rms_norm(x, layer_params["input_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)

    def proj(name, heads):
        p = layer_params["attn"][name]
        out = y @ p["kernel"].astype(cdt)
        if "bias" in p:
            out = out + p["bias"].astype(cdt)
        return out.reshape(b, w, heads, hd)

    q, k, v = proj("q_proj", h), proj("k_proj", kvh), proj("v_proj", kvh)
    q = apply_rope_at(q, pos, config.rope_theta, config._rope_scaling_key())
    k = apply_rope_at(k, pos, config.rope_theta, config._rope_scaling_key())
    attn, kept = attend(q, k, v)
    attn = attn.reshape(b, w, h * hd) @ layer_params["attn"]["o_proj"]["kernel"].astype(cdt)
    if config.post_block_norms:
        attn = rms_norm(attn, layer_params["attn_out_norm"]["scale"],
                        config.rms_norm_eps, config.rms_norm_offset)
    x = residual + attn

    residual = x
    y = rms_norm(x, layer_params["post_attn_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)
    if config.num_experts > 1:
        from ..ops.moe import moe_ffn

        y, _aux = moe_ffn(
            y,
            layer_params["mlp"]["router"]["kernel"],
            layer_params["mlp"]["experts"]["w_gate"],
            layer_params["mlp"]["experts"]["w_up"],
            layer_params["mlp"]["experts"]["w_down"],
            num_selected=config.num_experts_per_tok,
            capacity_factor=config.expert_capacity_factor,
            compute_dtype=cdt,
        )
    else:
        gate = y @ layer_params["mlp"]["gate_proj"]["kernel"].astype(cdt)
        up = y @ layer_params["mlp"]["up_proj"]["kernel"].astype(cdt)
        y = _mlp_act(config, gate) * up
        y = y @ layer_params["mlp"]["down_proj"]["kernel"].astype(cdt)
    if config.post_block_norms:
        y = rms_norm(y, layer_params["mlp_out_norm"]["scale"],
                     config.rms_norm_eps, config.rms_norm_offset)
    return residual + y, kept


def repeat_kv_cache(c, n_rep):
    """Physically tile a (B, S, Hkv, D) cache n_rep× over the head dim.

    The decode/prefill hot paths no longer call this — attention broadcasts
    over the GQA group dim inside the einsum instead of materializing
    n_rep× the KV bytes — but it stays as the reference semantics the
    grouped path is bit-checked against (tests/test_llama.py)."""
    if n_rep == 1:
        return c
    b, s, h, d = c.shape
    return jnp.broadcast_to(c[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def apply_rope_at(x, pos, theta, scaling=None):
    """RoPE for a window of W positions a row starting at a traced ``pos``:
    ``x`` (B, W, H, D), offset j of a row at absolute position ``pos + j``.
    Scalar ``pos`` starts the whole batch at one position; a (B,) ``pos``
    starts each row at its own (continuous-batching slots)."""
    b, w, h, d = x.shape
    freqs = jnp.asarray(_rope_freqs(d, theta, scaling), dtype=jnp.float32)
    at = pos.astype(jnp.float32)[..., None] + jnp.arange(w, dtype=jnp.float32)
    angles = at[..., None] * freqs  # (W, d/2), or (B, W, d/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(b, w, h, d).astype(x.dtype)


def _prefill_stack(config: LlamaConfig, params, input_ids):
    """Shared prefill layer stack: one full forward over the prompt →
    (pre-final-norm hidden (B, S, D), stacked K (L, B, S, kvh, hd), V)."""
    cdt = config.compute_dtype
    x = params["embed_tokens"]["embedding"].astype(cdt)[input_ids]
    if config.scale_embeddings:
        x = x * jnp.asarray(config.hidden_size**0.5, dtype=cdt)
    prefill_kw = dict(position_offset=0, attention_fn=None, collect_kv=True)
    layer_fn = functools.partial(_layer, config, **prefill_kw)

    if config.alternating_sliding_window:
        local_fn, global_fn = _alternating_fns(config, prefill_kw, remat=False)

        def pair_body(x, pair_params):
            lp0, lp1 = _pair_slices(pair_params)
            x, _a0, (k0, v0) = local_fn(lp0, x)
            x, _a1, (k1, v1) = global_fn(lp1, x)
            return x, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))

        # (L/2, 2, B, S, kvh, hd) -> (L, B, S, kvh, hd)
        x, (ks, vs) = lax.scan(pair_body, x, _pair_layers(params["layers"]))
        ks = ks.reshape(-1, *ks.shape[2:])
        vs = vs.reshape(-1, *vs.shape[2:])
    else:
        def body(x, layer_params):
            x, _aux, (k, v) = layer_fn(layer_params, x)
            return x, (k, v)

        x, (ks, vs) = lax.scan(body, x, params["layers"])  # ks: (L, B, S, kvh, hd)
    return x, ks, vs


def _prefill_head(config: LlamaConfig, params, x):
    """Final norm + LM head on hidden rows (..., D) → f32 (..., V): the
    prefill's gathered last rows, a decode step's, a verify window's."""
    cdt = config.compute_dtype
    x = rms_norm(x, params["final_norm"]["scale"], config.rms_norm_eps, config.rms_norm_offset)
    if config.tie_word_embeddings:
        logits = x @ params["embed_tokens"]["embedding"].astype(cdt).T
    else:
        logits = x @ params["lm_head"]["kernel"].astype(cdt)
    return _tanh_softcap(logits, config.final_logit_softcap).astype(jnp.float32)


def _pad_prefill_cache(ks, vs, max_len: int):
    s = ks.shape[2]
    pad = max_len - s
    return {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
    }


def llama_prefill(config: LlamaConfig, params, input_ids, max_len: int):
    """Full-forward prefill: one pass over the prompt (vs token-by-token
    decode), returning (last-position logits (B, V), filled KV cache sized
    ``max_len``)."""
    x, ks, vs = _prefill_stack(config, params, input_ids)
    return _prefill_head(config, params, x[:, -1]), _pad_prefill_cache(ks, vs, max_len)


def llama_prefill_at(config: LlamaConfig, params, input_ids, max_len: int, last_index):
    """Prefill a RIGHT-padded prompt batch: same full forward as
    :func:`llama_prefill`, but logits are taken at per-row ``last_index``
    (B,) — the last REAL prompt position — instead of position -1. Padding
    rows beyond ``last_index`` still write (garbage) KV, which is safe
    because decode masks ``k_pos <= pos`` and overwrites each position
    before it ever becomes attendable. The LM head runs only on the B
    gathered rows, not the full (B, S, V) logits."""
    x, ks, vs = _prefill_stack(config, params, input_ids)
    b = x.shape[0]
    x_last = x[jnp.arange(b), last_index]
    return _prefill_head(config, params, x_last), _pad_prefill_cache(ks, vs, max_len)


def _sliding_flags(config) -> tuple:
    """The per-layer ``sliding`` xs of the decode and verify scans: one
    array, even layers local (HF layer_types), for alternating
    sliding-window configs; nothing otherwise."""
    if not config.alternating_sliding_window:
        return ()
    return ((jnp.arange(config.num_hidden_layers) % 2) == 0,)


def _serving_step(config: LlamaConfig, params, cache, tokens, pos, kv_layout, attend_op):
    """Embed -> the layer loop over :func:`_step_block` -> the head, for a
    window ``tokens`` (B, W) at ``pos``. ``attend_op`` is the seam's operation
    (``kvcache.attend_step`` writes the store, ``attend_window`` only reads
    it); what the store is lives behind it and ``scan_layers``."""
    cdt = config.compute_dtype
    x = params["embed_tokens"]["embedding"].astype(cdt)[tokens]
    if config.scale_embeddings:
        x = x * jnp.asarray(config.hidden_size**0.5, dtype=cdt)
    attention = dict(
        scale=1.0 / math.sqrt(config.query_pre_attn_scalar or config.head_dim),
        softcap=config.attn_logit_softcap, window=config.sliding_window,
    )

    def block(x, layer_params, attend, sliding=None):
        attend = functools.partial(attend, pos=pos, sliding=sliding, **attention)
        return _step_block(config, layer_params, x, pos, attend)

    x, kept = scan_layers(
        attend_op, kv_layout, block, x, cache, params["layers"], *_sliding_flags(config)
    )
    return _prefill_head(config, params, x), kept


def llama_decode_step(config: LlamaConfig, params, cache, token, pos, *,
                      kv_layout=None):
    """One decode step: token (B, 1) at position ``pos`` — a traced scalar
    (whole batch in lockstep, the fused generate scan) or a traced (B,)
    vector (each row at its own position — continuous-batching slots).
    Returns (logits (B, V), new cache).

    ``kv_layout`` (a :class:`~accelerate_tpu.kvcache.PagedKVLayout`) says
    that ``cache`` leaves are the whole paged block pool; ``None`` that they
    are the dense arena. What follows from either is kvcache.py's."""
    logits, cache = _serving_step(config, params, cache, token, pos, kv_layout, attend_step)
    return logits[:, 0], cache


def llama_verify_step(config: LlamaConfig, params, cache, tokens, pos, *,
                      kv_layout=None):
    """Speculative-verify forward: ``tokens`` (B, W) — each row's carried
    token followed by W-1 draft tokens — at positions ``pos .. pos+W-1``
    (``pos`` a traced (B,) vector). Returns (logits (B, W, V) f32,
    window KV {"k","v"}: (L, B, W, kvh, hd)).

    The cache is consumed READ-ONLY: nothing is committed here. The caller
    decides the accepted prefix from the logits and commits exactly that
    many window columns via the backend's ``commit_window`` — so a rejected
    draft suffix never touches the persistent arena/pool and there is no
    rollback path."""
    return _serving_step(config, params, cache, tokens, pos, kv_layout, attend_window)


def create_llama(config: LlamaConfig, seed: int = 0, abstract: bool = False) -> Model:
    """``abstract=True`` builds the model with shape-only params
    (``jax.eval_shape``): prepare() then annotates shardings instead of
    placing arrays, and only ``train_step(...).lower`` works — the
    compile-analysis path for configs too big to materialize locally."""
    if abstract:
        params = jax.eval_shape(
            functools.partial(init_llama_params, config), jax.random.key(seed)
        )
    else:
        params = init_llama_params(config, jax.random.key(seed))
    return_aux = config.num_experts > 1
    overrides = {"attention_fn": None, "layer_stack_fn": None}

    def _rebind():
        model.apply_fn = functools.partial(
            llama_apply,
            config,
            return_aux=return_aux,
            **{k: v for k, v in overrides.items() if v is not None},
        )
        model._jitted_forward = None

    model = Model(
        functools.partial(llama_apply, config, return_aux=return_aux),
        params,
        name="llama" if not return_aux else "llama-moe",
    )
    model.config = config

    def set_attention_fn(attention_fn):
        """Accelerator.prepare hook: mesh-aware attention (ring/Ulysses)."""
        overrides["attention_fn"] = attention_fn
        _rebind()

    def set_layer_stack_fn(layer_stack_fn):
        """Accelerator.prepare hook: pipelined layer-stack execution (pp)."""
        overrides["layer_stack_fn"] = layer_stack_fn
        _rebind()

    model.set_attention_fn = set_attention_fn
    model.set_layer_stack_fn = set_layer_stack_fn
    model.canonical_loss = llama_loss
    if config.num_experts <= 1:
        # 1F1B contract (parallel/pp_1f1b.py); lazy so a later
        # set_attention_fn (ring/Ulysses) is picked up
        model.pipeline_parts = lambda: llama_pipeline_parts(
            config, overrides["attention_fn"]
        )
    return model


def llama_flops_per_token(config: LlamaConfig, seq_len: int, include_remat: bool = True) -> float:
    """Approximate *useful* training FLOPs/token (6ND + attention) for MFU.

    MFU convention counts fwd + 2×bwd only; rematerialized recompute is NOT
    useful work, so it is never included (``include_remat`` kept for
    hardware-utilization accounting, where full remat adds one extra fwd).
    """
    d, i, v = config.hidden_size, config.intermediate_size, config.vocab_size
    h, kvh, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    L = config.num_hidden_layers
    per_layer = 2 * d * (h * hd) + 2 * 2 * d * (kvh * hd) + 2 * (h * hd) * d  # qkvo
    per_layer += 3 * 2 * d * i  # swiglu
    attn = 2 * 2 * seq_len * h * hd  # qk + pv per token (upper bound; causal ≈ /2)
    embed = 2 * d * v  # lm head
    fwd = L * (per_layer + attn) + embed
    return 3.0 * fwd  # fwd + 2x bwd
