"""GPT-2-style causal LM: learned positions, pre-LN, fused-QKV, gelu MLP.

The model family behind the reference's Megatron GPT pretraining example
(/root/reference/examples/by_feature/megatron_lm_gpt_pretraining.py — there
it is provided by megatron-lm; here it is a first-class native family).
TPU-first like models/llama.py: stacked per-layer params scanned with
``lax.scan``, selectable remat policy, bf16 compute with fp32 logits, the
chunked fused-head CE protocol, and HF ``GPT2LMHeadModel`` checkpoint
interop in both directions (HF Conv1D stores (in, out) kernels, so weights
map without transposition).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jax.ad_checkpoint import checkpoint_name

from ..model import Model
from ..ops.attention import dispatch_attention
from ..parallel.sharding import constrain_activation, replicate_over_fsdp
from .bert import _apply_dense, _dense, layer_norm
from .llama import (
    _ce_from_hidden,
    _pallas_decode_override,
    _pallas_verify_override,
    _remat_policy,
    _scan_layers_over_pool,
    _use_pallas_attention,
    _write_kv_at,
    _write_kv_window,
    llama_ce_denominator,
    llama_loss,
)

__all__ = [
    "GPT2Config",
    "init_gpt2_params",
    "gpt2_apply",
    "create_gpt2",
    "gpt2_loss",
    "convert_hf_state_dict",
    "export_hf_state_dict",
    "upgrade_legacy_state",
]


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "nothing"  # "nothing" | "dots" | "minimal" | "full"
    attention_impl: str = "blockwise"  # "xla" | "blockwise" | "flash"
    attention_kv_block: int = 512
    attention_block_q: int = 2048
    scan_layers: bool = True
    use_chunked_ce: bool = False
    ce_chunk_size: int = 4096

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    def serving_family(self):
        """What the serving path asks of a family (models/family.py): every
        layer keeps keys and values, one head a query head."""
        from .family import ServingFamily

        return ServingFamily(
            prefill=gpt2_prefill, prefill_at=gpt2_prefill_at,
            decode_step=gpt2_decode_step, verify_step=gpt2_verify_step,
            kv_layers=self.num_hidden_layers, kv_heads=self.num_attention_heads,
            head_dim=self.head_dim,
        )

    @classmethod
    def gpt2_small(cls, **overrides) -> "GPT2Config":
        return cls(**overrides)

    @classmethod
    def gpt2_medium(cls, **overrides) -> "GPT2Config":
        return cls(**{**dict(hidden_size=1024, num_hidden_layers=24,
                             num_attention_heads=16), **overrides})

    @classmethod
    def gpt2_large(cls, **overrides) -> "GPT2Config":
        return cls(**{**dict(hidden_size=1280, num_hidden_layers=36,
                             num_attention_heads=20), **overrides})

    @classmethod
    def tiny(cls, **overrides) -> "GPT2Config":
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
        ), **overrides})


def init_gpt2_params(config: GPT2Config, key: jax.Array) -> dict:
    d, i, L = config.hidden_size, config.intermediate_size, config.num_hidden_layers
    dt = config.param_dtype
    keys = jax.random.split(key, 6)

    def stack_dense(k, in_dim, out_dim, scale=0.02):
        ks = jax.random.split(k, L)
        sub = [_dense(kk, in_dim, out_dim, dt, scale) for kk in ks]
        return {
            "kernel": jnp.stack([s["kernel"] for s in sub]),
            "bias": jnp.stack([s["bias"] for s in sub]),
        }

    def stack_ln():
        return {"scale": jnp.ones((L, d), dt), "bias": jnp.zeros((L, d), dt)}

    # GPT-2 initializes residual-path projections scaled down by sqrt(2L)
    resid_scale = 0.02 / np.sqrt(2 * L)
    kq, kk, kv = jax.random.split(keys[2], 3)
    return {
        "wte": {"embedding": (jax.random.normal(keys[0], (config.vocab_size, d)) * 0.02).astype(dt)},
        "wpe": {"embedding": (
            jax.random.normal(keys[1], (config.max_position_embeddings, d)) * 0.01
        ).astype(dt)},
        "layers": {
            "ln_1": stack_ln(),
            # q/k/v are separate params natively (HF fuses them into one
            # (d, 3d) Conv1D `c_attn`; conversion splits/fuses at the
            # checkpoint boundary). Slicing a fused mesh-sharded kernel in
            # the compiled graph makes GSPMD reshard each slice with
            # data-independent collective-permutes inside the layer scan —
            # XLA:CPU's concurrent thunk executor then starts them in
            # divergent orders across devices and deadlocks its rendezvous;
            # on TPU they are wasted ICI traffic. Separate params shard
            # cleanly like llama's q_proj/k_proj/v_proj.
            "attn": {
                "c_attn_q": stack_dense(kq, d, d),
                "c_attn_k": stack_dense(kk, d, d),
                "c_attn_v": stack_dense(kv, d, d),
                "c_proj": stack_dense(keys[3], d, d, scale=resid_scale),
            },
            "ln_2": stack_ln(),
            "mlp": {
                "c_fc": stack_dense(keys[4], d, i),
                "c_proj": stack_dense(keys[5], i, d, scale=resid_scale),
            },
        },
        "ln_f": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
    }


def _gpt2_layer(
    config: GPT2Config, lp, x, position_offset: int = 0,
    attention_fn: Optional[Any] = None, collect_kv: bool = False,
    segment_ids: Optional[Any] = None,
):
    cdt = config.compute_dtype
    b, s, d = x.shape
    h, hd = config.num_attention_heads, config.head_dim

    y = layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], config.layer_norm_eps)
    q = _apply_dense(lp["attn"]["c_attn_q"], y, cdt, tp_dim=1).reshape(b, s, h, hd)
    k = _apply_dense(lp["attn"]["c_attn_k"], y, cdt, tp_dim=1).reshape(b, s, h, hd)
    v = _apply_dense(lp["attn"]["c_attn_v"], y, cdt, tp_dim=1).reshape(b, s, h, hd)
    q, k, v = (constrain_activation(t, "heads") for t in (q, k, v))
    if attention_fn is not None:  # mesh-aware CP/SP attention from prepare()
        if segment_ids is not None:
            # packed batches compose with CP/SP (labels shard with the
            # sequence — see models/llama.py _attention)
            attn = attention_fn(q, k, v, causal=True, segment_ids=segment_ids)
        else:
            attn = attention_fn(q, k, v, causal=True)
    else:
        attn = dispatch_attention(
            config.attention_impl, q, k, v, causal=True, q_offset=position_offset,
            kv_block=config.attention_kv_block, block_q=config.attention_block_q,
            segment_ids=segment_ids,
        )
    attn = _apply_dense(lp["attn"]["c_proj"], attn.reshape(b, s, d), cdt, tp_dim=0)
    attn = checkpoint_name(attn, "attn_block_out")  # saved under remat "minimal"
    x = constrain_activation(x + attn)

    y = layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], config.layer_norm_eps)
    # gelu_new (tanh approximation) — matches HF GPT-2 exactly
    y = jax.nn.gelu(_apply_dense(lp["mlp"]["c_fc"], y, cdt, tp_dim=1), approximate=True)
    y = _apply_dense(lp["mlp"]["c_proj"], y, cdt, tp_dim=0)
    y = checkpoint_name(y, "mlp_block_out")
    out = constrain_activation(x + y)
    if collect_kv:
        return out, (k, v)
    return out


def gpt2_apply(
    config: GPT2Config,
    params: dict,
    input_ids: jax.Array,
    position_offset: int = 0,
    attention_fn: Optional[Any] = None,
    layer_stack_fn: Optional[Any] = None,
    segment_ids: Optional[Any] = None,
    position_ids: Optional[Any] = None,
):
    """(B, S) int tokens → (B, S, V) fp32 logits, or the chunked-CE protocol
    dict {"hidden", "head_kernel"} when ``config.use_chunked_ce`` (the head is
    always tied to wte, as in GPT-2). ``attention_fn``/``layer_stack_fn`` are
    the prepare-time CP/SP and PP hooks (same contract as llama_apply)."""
    cdt = config.compute_dtype
    b, s = input_ids.shape
    if s + position_offset > config.max_position_embeddings:
        # learned positions clamp silently in compiled gathers (mode='clip');
        # unlike RoPE there is no valid extrapolation — fail loudly instead
        raise ValueError(
            f"sequence end {s + position_offset} exceeds "
            f"max_position_embeddings={config.max_position_embeddings}"
        )
    # cast BEFORE the gather: the replication then moves bf16, not f32
    table = replicate_over_fsdp(params["wte"]["embedding"].astype(cdt), keep_tp=False)
    x = table[input_ids]
    wpe = params["wpe"]["embedding"].astype(cdt)
    if position_ids is not None:
        # packed rows: learned positions restart at each document
        x = constrain_activation(x + wpe[position_ids])
    else:
        pos = jnp.arange(s) + position_offset
        x = constrain_activation(x + wpe[pos][None])

    layer_fn = functools.partial(
        _gpt2_layer, config, position_offset=position_offset,
        attention_fn=attention_fn, segment_ids=segment_ids,
    )
    if config.remat_policy != "full":
        layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(config.remat_policy))

    if layer_stack_fn is not None:
        x, _aux = layer_stack_fn(params["layers"], x, lambda lp, x: (layer_fn(lp, x), jnp.float32(0.0)))
    elif config.scan_layers:
        def body(x, lp):
            return layer_fn(lp, x), None

        x, _ = lax.scan(body, x, params["layers"])
    else:
        for li in range(config.num_hidden_layers):
            lp = jax.tree_util.tree_map(lambda p: p[li], params["layers"])
            x = layer_fn(lp, x)

    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], config.layer_norm_eps)
    head = params["wte"]["embedding"].T
    if config.use_chunked_ce:
        return {"hidden": x, "head_kernel": head}
    logits = (x @ replicate_over_fsdp(head.astype(cdt))).astype(jnp.float32)
    return constrain_activation(logits, "vocab")


def create_gpt2(config: GPT2Config, seed: int = 0) -> Model:
    params = init_gpt2_params(config, jax.random.key(seed))
    overrides = {"attention_fn": None, "layer_stack_fn": None}

    def _rebind():
        model.apply_fn = functools.partial(
            gpt2_apply, config, **{k: v for k, v in overrides.items() if v is not None}
        )
        model._jitted_forward = None

    model = Model(functools.partial(gpt2_apply, config), params, name="gpt2")
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
    model.canonical_loss = gpt2_loss
    model.upgrade_state_fn = upgrade_legacy_state
    # 1F1B contract (parallel/pp_1f1b.py); lazy so a later set_attention_fn
    # (ring/Ulysses) is picked up
    model.pipeline_parts = lambda: gpt2_pipeline_parts(
        config, overrides["attention_fn"]
    )
    return model


# the output protocol (logits | {"hidden","head_kernel"}) matches llama's, so
# the shifted-label masked CE (incl. the fused chunked path) is shared
gpt2_loss = llama_loss


def gpt2_pipeline_parts(config: GPT2Config, attention_fn=None):
    """(embed_fn, stage_fn, head_loss_fn, denominator_fn) for the
    hand-scheduled 1F1B pipeline (parallel/pp_1f1b.py) — same contract as
    llama_pipeline_parts; the CE tail is the shared ``_ce_from_hidden`` so
    the pipelined loss provably matches :func:`gpt2_loss`."""
    cdt = config.compute_dtype
    layer_fn = functools.partial(
        _gpt2_layer, config, position_offset=0, attention_fn=attention_fn
    )
    if config.remat_policy != "full":
        layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(config.remat_policy))

    def embed_fn(params, mb):
        ids = mb["input_ids"]
        s = ids.shape[1]
        x = params["wte"]["embedding"].astype(cdt)[ids]
        x = x + params["wpe"]["embedding"].astype(cdt)[jnp.arange(s)][None]
        return constrain_activation(x)

    def stage_fn(stage_params, h):
        def body(h, lp):
            return layer_fn(lp, h), None

        h, _ = lax.scan(body, h, stage_params)
        return h

    def head_loss_fn(params, h, mb):
        x = layer_norm(
            h, params["ln_f"]["scale"], params["ln_f"]["bias"], config.layer_norm_eps
        )
        head = params["wte"]["embedding"].T
        labels = mb.get("labels")
        mask = mb.get("loss_mask")
        if labels is None:
            labels = mb["input_ids"][:, 1:]
            x = x[:, :-1]
        return _ce_from_hidden(config, x, head, labels, mask, reduction="sum")

    return embed_fn, stage_fn, head_loss_fn, llama_ce_denominator


# ------------------------------------------------------------ generation
def _gpt2_prefill_stack(config: GPT2Config, params, input_ids, max_len: int):
    """Shared prefill layer stack → (pre-ln_f hidden (B, S, D), cache
    padded to ``max_len``)."""
    cdt = config.compute_dtype
    b, s = input_ids.shape
    if max_len > config.max_position_embeddings:
        raise ValueError(
            f"generation length {max_len} exceeds max_position_embeddings="
            f"{config.max_position_embeddings}: learned positions cannot "
            "extrapolate (the compiled gather would silently clamp)"
        )
    x = params["wte"]["embedding"].astype(cdt)[input_ids]
    x = x + params["wpe"]["embedding"].astype(cdt)[jnp.arange(s)][None]

    layer_fn = functools.partial(_gpt2_layer, config, collect_kv=True)

    def body(x, lp):
        x, (k, v) = layer_fn(lp, x)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"])  # (L, B, S, h, hd)
    pad = max_len - s
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
    }
    return x, cache


def _gpt2_head(config: GPT2Config, params, x):
    """Final layer norm + tied LM head on (B, D) rows → f32 (B, V)."""
    cdt = config.compute_dtype
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], config.layer_norm_eps)
    return (x @ params["wte"]["embedding"].astype(cdt).T).astype(jnp.float32)


def gpt2_prefill(config: GPT2Config, params, input_ids, max_len: int):
    """One full forward over the prompt → (last-position logits (B, V),
    KV cache padded to ``max_len``). Same contract as llama_prefill."""
    x, cache = _gpt2_prefill_stack(config, params, input_ids, max_len)
    return _gpt2_head(config, params, x[:, -1]), cache


def gpt2_prefill_at(config: GPT2Config, params, input_ids, max_len: int, last_index):
    """Prefill a RIGHT-padded prompt batch with logits at per-row
    ``last_index`` (B,) — same contract as :func:`~.llama.llama_prefill_at`."""
    x, cache = _gpt2_prefill_stack(config, params, input_ids, max_len)
    x_last = x[jnp.arange(x.shape[0]), last_index]
    return _gpt2_head(config, params, x_last), cache


def _gpt2_decode_layer(config: GPT2Config, lp, x, cache_k, cache_v, pos,
                       attention_override=None):
    """One block, one new position; updates the (B, max_len, h, hd) caches.
    ``pos`` is a traced scalar (lockstep batch) or (B,) vector (per-row
    positions — continuous-batching slots), same contract as llama's
    ``_decode_layer`` including the Pallas ``attention_override`` hook
    (takes the new-position q/k/v, owns the KV commit, returns the
    attended output plus updated caches)."""
    cdt = config.compute_dtype
    b, s, d = x.shape  # s == 1
    h, hd = config.num_attention_heads, config.head_dim

    y = layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], config.layer_norm_eps)
    q = _apply_dense(lp["attn"]["c_attn_q"], y, cdt).reshape(b, s, h, hd)
    k = _apply_dense(lp["attn"]["c_attn_k"], y, cdt).reshape(b, s, h, hd)
    v = _apply_dense(lp["attn"]["c_attn_v"], y, cdt).reshape(b, s, h, hd)
    if attention_override is not None:
        attn, cache_k, cache_v = attention_override(q, k, v)
        attn = attn.astype(cdt)
    else:
        cache_k = _write_kv_at(cache_k, k, pos)
        cache_v = _write_kv_at(cache_v, v, pos)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q * (1.0 / np.sqrt(hd)), cache_k.astype(cdt)
        ).astype(jnp.float32)
        k_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        pos_b = pos if jnp.ndim(pos) == 0 else pos[:, None, None, None]
        scores = jnp.where(k_pos <= pos_b, scores, -1e6)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(cdt), cache_v.astype(cdt))
    attn = _apply_dense(lp["attn"]["c_proj"], attn.reshape(b, s, d), cdt)
    x = x + attn

    y = layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], config.layer_norm_eps)
    y = jax.nn.gelu(_apply_dense(lp["mlp"]["c_fc"], y, cdt), approximate=True)
    y = _apply_dense(lp["mlp"]["c_proj"], y, cdt)
    return x + y, cache_k, cache_v


def gpt2_decode_step(config: GPT2Config, params, cache, token, pos, *,
                     kv_layout=None):
    """One decode step: token (B, 1) at traced position ``pos`` (scalar, or
    (B,) per-row positions for continuous-batching slots) → (logits (B, V),
    new cache). Same contract as llama_decode_step, including the optional
    paged ``kv_layout`` (the layer loop carries the pool whole; a layer's
    blocks are gathered to a dense view before it attends and the new column
    committed back after, or read in place by the Pallas kernel)."""
    cdt = config.compute_dtype
    x = params["wte"]["embedding"].astype(cdt)[token]
    wpe = params["wpe"]["embedding"].astype(cdt)
    if jnp.ndim(pos) == 0:
        x = x + jnp.take(wpe, pos, axis=0)[None, None]
    else:
        x = x + jnp.take(wpe, pos, axis=0)[:, None]

    pallas = _use_pallas_attention(config, kv_layout)

    def paged_step(x, lp, ck, cv, layer):
        if pallas:
            override = _pallas_decode_override(config, kv_layout, pos, ck, cv, layer)
            return _gpt2_decode_layer(config, lp, x, None, None, pos,
                                      attention_override=override)
        x, vk, vv = _gpt2_decode_layer(
            config, lp, x, kv_layout.view(ck, layer), kv_layout.view(cv, layer), pos
        )
        return x, kv_layout.commit(ck, vk, pos, layer), kv_layout.commit(cv, vv, pos, layer)

    def dense_body(x, inputs):
        lp, ck, cv = inputs
        x, ck, cv = _gpt2_decode_layer(config, lp, x, ck, cv, pos)
        return x, (ck, cv)

    if kv_layout is not None:
        x, new_cache = _scan_layers_over_pool(paged_step, x, cache, params["layers"])
    else:
        x, (new_k, new_v) = lax.scan(
            dense_body, x, (params["layers"], cache["k"], cache["v"])
        )
        new_cache = {"k": new_k, "v": new_v}
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], config.layer_norm_eps)
    logits = x @ params["wte"]["embedding"].astype(cdt).T
    return logits[:, 0].astype(jnp.float32), new_cache


def _gpt2_verify_layer(config: GPT2Config, lp, x, cache_k, cache_v, pos,
                       attention_override=None):
    """One block over a W-token speculative-verify window at positions
    ``pos .. pos+W-1`` (``pos`` a traced (B,) vector). Same read-only-cache
    contract as llama's ``_verify_layer``: the window's K/V go into a
    temporary scatter-written copy for the causal attend (or straight to
    the Pallas ``attention_override``, which attends them in-register),
    and the raw window K/V are returned for the caller's accepted-prefix
    commit."""
    cdt = config.compute_dtype
    b, w, d = x.shape
    h, hd = config.num_attention_heads, config.head_dim

    y = layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], config.layer_norm_eps)
    q = _apply_dense(lp["attn"]["c_attn_q"], y, cdt).reshape(b, w, h, hd)
    k = _apply_dense(lp["attn"]["c_attn_k"], y, cdt).reshape(b, w, h, hd)
    v = _apply_dense(lp["attn"]["c_attn_v"], y, cdt).reshape(b, w, h, hd)
    win_k, win_v = k, v
    if attention_override is not None:
        attn = attention_override(q, k, v).astype(cdt)
    else:
        cache_k = _write_kv_window(cache_k, k, pos)
        cache_v = _write_kv_window(cache_v, v, pos)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q * (1.0 / np.sqrt(hd)), cache_k.astype(cdt)
        ).astype(jnp.float32)
        k_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        q_idx = lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        pos_b = pos[:, None, None, None]
        scores = jnp.where(k_pos <= pos_b + q_idx, scores, -1e6)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(cdt), cache_v.astype(cdt))
    attn = _apply_dense(lp["attn"]["c_proj"], attn.reshape(b, w, d), cdt)
    x = x + attn

    y = layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], config.layer_norm_eps)
    y = jax.nn.gelu(_apply_dense(lp["mlp"]["c_fc"], y, cdt), approximate=True)
    y = _apply_dense(lp["mlp"]["c_proj"], y, cdt)
    return x + y, win_k, win_v


def gpt2_verify_step(config: GPT2Config, params, cache, tokens, pos, *,
                     kv_layout=None):
    """Speculative-verify forward: ``tokens`` (B, W) at positions
    ``pos .. pos+W-1`` → (logits (B, W, V) f32, window KV (L, B, W, h, hd)).
    Same contract as :func:`~.llama.llama_verify_step`: the cache is
    read-only here; the caller commits the accepted prefix. Learned
    positions use a clamping ``jnp.take`` (matching decode) — padded
    window positions past ``max_position_embeddings`` clamp harmlessly
    because their logits are discarded by the engine's length mask."""
    cdt = config.compute_dtype
    b, w = tokens.shape
    x = params["wte"]["embedding"].astype(cdt)[tokens]
    wpe = params["wpe"]["embedding"].astype(cdt)
    abs_pos = pos[:, None] + jnp.arange(w, dtype=pos.dtype)[None, :]  # (B, W)
    x = x + jnp.take(wpe, abs_pos, axis=0)

    pallas = _use_pallas_attention(config, kv_layout)

    def paged_body(x, inputs):
        # the pool is only read here: a loop invariant the body closes over,
        # addressed by layer like the decode step's (never sliced as xs)
        lp, layer = inputs
        ck, cv = cache["k"], cache["v"]
        if pallas:
            override = _pallas_verify_override(config, kv_layout, pos, ck, cv, layer)
            x, wk, wv = _gpt2_verify_layer(config, lp, x, None, None, pos,
                                           attention_override=override)
        else:
            x, wk, wv = _gpt2_verify_layer(
                config, lp, x, kv_layout.view(ck, layer), kv_layout.view(cv, layer), pos
            )
        return x, (wk, wv)

    def dense_body(x, inputs):
        lp, ck, cv = inputs
        x, wk, wv = _gpt2_verify_layer(config, lp, x, ck, cv, pos)
        return x, (wk, wv)

    if kv_layout is not None:
        layers = jnp.arange(config.num_hidden_layers, dtype=jnp.int32)
        x, (win_k, win_v) = lax.scan(paged_body, x, (params["layers"], layers))
    else:
        x, (win_k, win_v) = lax.scan(
            dense_body, x, (params["layers"], cache["k"], cache["v"])
        )
    x = layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], config.layer_norm_eps)
    logits = x @ params["wte"]["embedding"].astype(cdt).T
    return logits.astype(jnp.float32), {"k": win_k, "v": win_v}


def upgrade_legacy_state(tree: dict) -> dict:
    """Migrate a native checkpoint saved before the per-projection q/k/v
    split (when ``layers.attn`` held one fused (L, d, 3d) ``c_attn``) to the
    current layout. Trees already in the current layout pass through
    unchanged, so this is safe to run on every load (wired as the model's
    ``upgrade_state_fn``)."""
    try:
        attn = tree["layers"]["attn"]
    except (KeyError, TypeError):
        return tree
    if "c_attn" not in attn:
        return tree
    fused = attn["c_attn"]
    kernel = np.asarray(fused["kernel"])  # (L, d, 3d)
    bias = np.asarray(fused["bias"])  # (L, 3d)
    d = kernel.shape[-1] // 3
    new_attn = {k: v for k, v in attn.items() if k != "c_attn"}
    for idx, name in enumerate(("c_attn_q", "c_attn_k", "c_attn_v")):
        new_attn[name] = {
            "kernel": kernel[..., idx * d : (idx + 1) * d],
            "bias": bias[..., idx * d : (idx + 1) * d],
        }
    new_layers = {k: v for k, v in tree["layers"].items() if k != "attn"}
    new_layers["attn"] = new_attn
    out = {k: v for k, v in tree.items() if k != "layers"}
    out["layers"] = new_layers
    return out


# ------------------------------------------------------------ HF interop
def convert_hf_state_dict(config: GPT2Config, flat: dict) -> dict:
    """HF ``GPT2LMHeadModel.state_dict()`` (numpy arrays) → our stacked
    pytree. HF's Conv1D keeps (in, out) kernels, so no transposition; its
    fused (d, 3d) ``c_attn`` is split into our native per-projection
    q/k/v params here, at the checkpoint boundary (init_gpt2_params explains
    why the compiled graph never slices a fused kernel)."""
    dt = config.param_dtype
    d = config.hidden_size
    L = config.num_hidden_layers

    def get(name):
        return jnp.asarray(np.asarray(flat[name]), dtype=dt)

    def stacked(suffix):
        return jnp.stack([get(f"transformer.h.{i}.{suffix}") for i in range(L)])

    qkv_kernel = stacked("attn.c_attn.weight")  # (L, d, 3d)
    qkv_bias = stacked("attn.c_attn.bias")  # (L, 3d)
    return {
        "wte": {"embedding": get("transformer.wte.weight")},
        "wpe": {"embedding": get("transformer.wpe.weight")},
        "layers": {
            "ln_1": {"scale": stacked("ln_1.weight"), "bias": stacked("ln_1.bias")},
            "attn": {
                "c_attn_q": {
                    "kernel": qkv_kernel[:, :, :d],
                    "bias": qkv_bias[:, :d],
                },
                "c_attn_k": {
                    "kernel": qkv_kernel[:, :, d : 2 * d],
                    "bias": qkv_bias[:, d : 2 * d],
                },
                "c_attn_v": {
                    "kernel": qkv_kernel[:, :, 2 * d :],
                    "bias": qkv_bias[:, 2 * d :],
                },
                "c_proj": {
                    "kernel": stacked("attn.c_proj.weight"),
                    "bias": stacked("attn.c_proj.bias"),
                },
            },
            "ln_2": {"scale": stacked("ln_2.weight"), "bias": stacked("ln_2.bias")},
            "mlp": {
                "c_fc": {
                    "kernel": stacked("mlp.c_fc.weight"),
                    "bias": stacked("mlp.c_fc.bias"),
                },
                "c_proj": {
                    "kernel": stacked("mlp.c_proj.weight"),
                    "bias": stacked("mlp.c_proj.bias"),
                },
            },
        },
        "ln_f": {"scale": get("transformer.ln_f.weight"), "bias": get("transformer.ln_f.bias")},
    }


def export_hf_state_dict(config: GPT2Config, params: dict) -> dict:
    """Inverse of :func:`convert_hf_state_dict` (torch-ecosystem export).
    ``lm_head.weight`` is emitted tied to wte, as HF expects."""
    out = {
        "transformer.wte.weight": params["wte"]["embedding"],
        "transformer.wpe.weight": params["wpe"]["embedding"],
        "transformer.ln_f.weight": params["ln_f"]["scale"],
        "transformer.ln_f.bias": params["ln_f"]["bias"],
        "lm_head.weight": params["wte"]["embedding"],
    }
    lay = params["layers"]
    attn = lay["attn"]
    # re-fuse native q/k/v into HF's (d, 3d) Conv1D c_attn layout
    qkv_kernel = jnp.concatenate(
        [attn["c_attn_q"]["kernel"], attn["c_attn_k"]["kernel"],
         attn["c_attn_v"]["kernel"]], axis=-1,
    )
    qkv_bias = jnp.concatenate(
        [attn["c_attn_q"]["bias"], attn["c_attn_k"]["bias"],
         attn["c_attn_v"]["bias"]], axis=-1,
    )
    names = {
        "ln_1.weight": lay["ln_1"]["scale"],
        "ln_1.bias": lay["ln_1"]["bias"],
        "attn.c_attn.weight": qkv_kernel,
        "attn.c_attn.bias": qkv_bias,
        "attn.c_proj.weight": lay["attn"]["c_proj"]["kernel"],
        "attn.c_proj.bias": lay["attn"]["c_proj"]["bias"],
        "ln_2.weight": lay["ln_2"]["scale"],
        "ln_2.bias": lay["ln_2"]["bias"],
        "mlp.c_fc.weight": lay["mlp"]["c_fc"]["kernel"],
        "mlp.c_fc.bias": lay["mlp"]["c_fc"]["bias"],
        "mlp.c_proj.weight": lay["mlp"]["c_proj"]["kernel"],
        "mlp.c_proj.bias": lay["mlp"]["c_proj"]["bias"],
    }
    for i in range(config.num_hidden_layers):
        for suffix, stacked in names.items():
            out[f"transformer.h.{i}.{suffix}"] = stacked[i]
    return {k: np.asarray(v) for k, v in out.items()}
