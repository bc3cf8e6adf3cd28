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

from ..kvcache import attend_step, attend_window, scan_layers
from ..model import Model
from ..ops.attention import dispatch_attention
from ..parallel.sharding import constrain_activation, replicate_over_fsdp
from .bert import _apply_dense, _dense, layer_norm
from .llama import _ce_from_hidden, checkpoint_layer, llama_ce_denominator, llama_loss

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
    """GPT-2 (learned positions, LayerNorm, fused QKV, GELU MLP, tied head).

    ``remat_policy`` is ``LlamaConfig``'s: ``"full"`` (no checkpoint), ``"dots"``
    / ``"dots_no_batch"`` (matmul outputs saved), ``"minimal"`` (the two block
    outputs a layer), ``"nothing"`` (a layer's input only), or ``"auto"``, the
    default: ``Accelerator.train_step`` keeps the first of dots, full, minimal,
    nothing whose compiled step fits the device; elsewhere it is ``"nothing"``.
    """

    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "auto"  # see the class docstring
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

    layer_fn = checkpoint_layer(config, functools.partial(
        _gpt2_layer, config, position_offset=position_offset,
        attention_fn=attention_fn, segment_ids=segment_ids,
    ))

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
    layer_fn = checkpoint_layer(config, functools.partial(
        _gpt2_layer, config, position_offset=0, attention_fn=attention_fn
    ))

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
    """Final layer norm + tied LM head on (..., D) rows → f32 (..., V)."""
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


def _gpt2_step_block(config: GPT2Config, lp, x, attend):
    """One block over a window of W new positions a row, ``x`` (B, W, D); W = 1
    is a decode step. Positions are learned and already in ``x``.
    ``attend(q, k, v) -> (attn, kept)`` is the cache seam, same contract as
    llama's ``_step_block``: it owns the write, the attend path and the
    attention, and ``kept`` (the updated store, or the window's keys and
    values) is returned beside the new ``x``."""
    cdt = config.compute_dtype
    b, w, d = x.shape
    h, hd = config.num_attention_heads, config.head_dim

    y = layer_norm(x, lp["ln_1"]["scale"], lp["ln_1"]["bias"], config.layer_norm_eps)
    q = _apply_dense(lp["attn"]["c_attn_q"], y, cdt).reshape(b, w, h, hd)
    k = _apply_dense(lp["attn"]["c_attn_k"], y, cdt).reshape(b, w, h, hd)
    v = _apply_dense(lp["attn"]["c_attn_v"], y, cdt).reshape(b, w, h, hd)
    attn, kept = attend(q, k, v)
    attn = _apply_dense(lp["attn"]["c_proj"], attn.reshape(b, w, d), cdt)
    x = x + attn

    y = layer_norm(x, lp["ln_2"]["scale"], lp["ln_2"]["bias"], config.layer_norm_eps)
    y = jax.nn.gelu(_apply_dense(lp["mlp"]["c_fc"], y, cdt), approximate=True)
    y = _apply_dense(lp["mlp"]["c_proj"], y, cdt)
    return x + y, kept


def _gpt2_serving_step(config: GPT2Config, params, cache, tokens, pos, kv_layout, attend_op):
    """Embed -> the layer loop over :func:`_gpt2_step_block` -> the head, for
    a window ``tokens`` (B, W) at positions ``pos .. pos+W-1``. Learned
    positions use a clamping ``jnp.take``: a padded window position past
    ``max_position_embeddings`` clamps harmlessly, its logits are discarded
    by the engine's length mask."""
    cdt = config.compute_dtype
    x = params["wte"]["embedding"].astype(cdt)[tokens]
    at = pos[..., None] + jnp.arange(tokens.shape[1], dtype=pos.dtype)  # (W,) or (B, W)
    x = x + jnp.take(params["wpe"]["embedding"].astype(cdt), at, axis=0)

    def block(x, lp, attend):
        return _gpt2_step_block(config, lp, x, functools.partial(attend, pos=pos))

    x, kept = scan_layers(attend_op, kv_layout, block, x, cache, params["layers"])
    return _gpt2_head(config, params, x), kept


def gpt2_decode_step(config: GPT2Config, params, cache, token, pos, *,
                     kv_layout=None):
    """One decode step: token (B, 1) at traced position ``pos`` (scalar, or
    (B,) per-row positions for continuous-batching slots) -> (logits (B, V),
    new cache). Same contract as llama_decode_step, including the optional
    paged ``kv_layout``."""
    logits, cache = _gpt2_serving_step(config, params, cache, token, pos, kv_layout, attend_step)
    return logits[:, 0], cache


def gpt2_verify_step(config: GPT2Config, params, cache, tokens, pos, *,
                     kv_layout=None):
    """Speculative-verify forward: ``tokens`` (B, W) at positions
    ``pos .. pos+W-1`` -> (logits (B, W, V) f32, window KV (L, B, W, h, hd)).
    Same contract as :func:`~.llama.llama_verify_step`: the cache is
    read-only here; the caller commits the accepted prefix."""
    return _gpt2_serving_step(config, params, cache, tokens, pos, kv_layout, attend_window)


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
