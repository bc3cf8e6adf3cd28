"""Inference utilities: compiled greedy/sampled generation with KV cache.

TPU-native analogue of the reference's ``inference.py`` (prepare_pippy
pipeline inference, :126) + the per-token generation path its
big_model_inference benchmark measures. Here generation is ONE compiled
``lax.scan`` over decode steps (no per-token Python/dispatch overhead, no
per-layer weight onload like the reference's hook path, SURVEY §3.5) and the
model can be sharded over any mesh (TP/FSDP axes) — pipeline inference is
just the pp mesh axis.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .model import Model

__all__ = [
    "generate",
    "prepare_inference",
    "generate_cache_stats",
    "last_generate_stats",
]

# compiled generate() programs kept per Model (serving loops with varying
# prompt lengths compile per length; this caps host-side executable count).
# ACCELERATE_GENERATE_CACHE_MAX tunes it for serving deployments whose
# bucket grid (batch pow-2s × prompt lengths × total-len multiples) is
# wider than the default. The env var is read when a model's cache is
# first attached (not at import), so deployments can set it after import
# without import-order games; this constant is only the fallback default.
_GENERATE_CACHE_MAX = 16


def _generate_cache_max() -> int:
    raw = os.environ.get("ACCELERATE_GENERATE_CACHE_MAX")
    if raw is None:
        return _GENERATE_CACHE_MAX
    try:
        return max(1, int(raw))
    except ValueError:
        return _GENERATE_CACHE_MAX

# guards the lazy attach of a model's LRU + lock (double-checked below);
# the per-model lock then guards that model's OrderedDict — concurrent
# serving threads mutating it unlocked can corrupt the dict
_CACHE_ATTACH_LOCK = threading.Lock()


def _model_generate_cache(model: Model):
    cache = getattr(model, "_generate_cache", None)
    lock = getattr(model, "_generate_cache_lock", None)
    if cache is None or lock is None:
        with _CACHE_ATTACH_LOCK:
            cache = getattr(model, "_generate_cache", None)
            lock = getattr(model, "_generate_cache_lock", None)
            if lock is None:
                lock = model._generate_cache_lock = threading.Lock()
            if cache is None:
                # env read HERE (attach time), so the bound is whatever the
                # deployment set before its first generate on this model
                model._generate_cache_max = _generate_cache_max()
                cache = model._generate_cache = OrderedDict()
    return cache, lock


def generate(
    model: Model,
    input_ids,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    seed: int = 0,
    pad_to: Optional[int] = None,
    *,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: Optional[int] = None,
    kv_backend: str = "dense",
    kv_block_size: int = 16,
):
    """Greedy (temperature=0) or sampled generation for the causal-LM
    families (llama/mixtral/mistral, gpt2, lfm2): the model's config hands
    over its family's step functions (``config.serving_family()``).

    Prefill runs the full forward once; decode is a single compiled scan with
    a static-size KV cache. ``top_k``/``top_p`` (nucleus) filter the sampled
    distribution; ``eos_token_id`` freezes a finished sequence (subsequent
    positions emit ``pad_token_id``, defaulting to the EOS id — HF's
    convention when pad is unset). Returns (B, prompt+new) token ids.

    ``kv_backend`` selects the decode-scan KV layout: ``"dense"`` (default,
    in-place writes at ``pos``), ``"paged"`` (the prefill cache is re-laid as
    a block pool with identity tables and decode runs through the same
    gather/commit ops as the continuous engine — bitwise-identical greedy
    outputs in f32), or ``"paged_int8"`` (pool stored int8 with per-block
    scales). Paged rounds the cache length up to a ``kv_block_size``
    multiple, which only enlarges the KV pool with extra masked positions —
    the decode scan always runs exactly ``max_new_tokens`` steps, so the
    output token count is unchanged.
    """
    from .kvcache import KV_BACKENDS, PagedKVLayout, pool_from_dense

    if kv_backend not in KV_BACKENDS:
        raise ValueError(
            f"kv_backend must be one of {KV_BACKENDS}, got {kv_backend!r}"
        )
    paged = kv_backend != "dense"
    if paged and kv_block_size < 1:
        raise ValueError(f"kv_block_size must be >= 1, got {kv_block_size}")
    config = model.config
    family = config.serving_family()
    prefill_fn, decode_fn = family.prefill, family.decode_step
    input_ids = jnp.asarray(input_ids, dtype=jnp.int32)
    b, prompt_len = input_ids.shape
    total_len = prompt_len + max_new_tokens
    if pad_to is not None:
        total_len = max(total_len, pad_to)
    if paged:  # the pool relay needs whole blocks
        total_len = -(-total_len // kv_block_size) * kv_block_size
    if pad_token_id is None:
        pad_token_id = eos_token_id if eos_token_id is not None else 0

    # ONE jitted end-to-end program (prefill + decode scan), cached on the
    # model. Building it eagerly per call would re-trace everything every
    # time — decode_body is a fresh closure, so even lax.scan's internal
    # cache misses and each generate() paid a full recompile (3.4 s/call
    # for the tiny model on CPU — the train-step double-compile bug's
    # sibling). The key holds only
    # STRUCTURAL choices (shapes + which sampling branches exist);
    # temperature/top_p/token ids are traced operands, so a serving loop
    # varying them per request reuses one program. Varying prompt lengths
    # still compile per length (static shapes) — pass ``pad_to`` to bucket
    # them; an LRU bound caps the compiled-program count either way.
    temp_on = temperature > 0.0
    top_k_width = (
        top_k if (temp_on and top_k is not None and 0 < top_k < config.vocab_size)
        else None
    )  # structural: sets the lax.top_k width
    top_p_on = temp_on and top_p is not None and top_p < 1.0
    eos_on = eos_token_id is not None
    cache_key = (
        type(config).__name__, b, prompt_len, total_len, max_new_tokens,
        temp_on, top_k_width, top_p_on, eos_on,
        kv_backend, kv_block_size if paged else None,
    )
    jit_cache, cache_lock = _model_generate_cache(model)
    with cache_lock:
        run = jit_cache.get(cache_key)
        if run is not None:
            jit_cache.move_to_end(cache_key)
    if run is None:

        def sample(logits, key, temp, p_threshold):
            if not temp_on:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / temp
            # top_k in (None, 0) means unfiltered (HF convention for 0)
            if top_k_width is not None:
                kth = lax.top_k(logits, top_k_width)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p_on:
                # nucleus: keep the smallest prefix of the sorted
                # distribution with cumulative probability >= top_p (the top
                # token always survives — the cumulative sum is exclusive,
                # so element 0 is 0)
                sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                cum = jnp.cumsum(probs, axis=-1) - probs
                cutoff_idx = jnp.maximum(
                    jnp.sum((cum < p_threshold).astype(jnp.int32), axis=-1) - 1, 0
                )
                cutoff = jnp.take_along_axis(
                    sorted_logits, cutoff_idx[..., None], axis=-1
                )
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

        def _run(params, input_ids, key, temp, p_threshold, eos_id, pad_id):
            # prefill: ONE full forward fills the cache (O(S) matmul work
            # vs O(S²) for token-by-token decode over the prompt)
            # a family with step counters returns them third: unused here
            logits, cache, *_ = prefill_fn(config, params, input_ids, total_len)
            if paged:
                # re-lay as a block pool with identity tables: decode now
                # exercises the engine's gather/commit ops inside this same
                # program (still ONE executable per cache_key)
                cache, tables = pool_from_dense(
                    cache, kv_block_size, quantized=kv_backend == "paged_int8"
                )
                kv_layout = PagedKVLayout(
                    tables, kv_block_size, config.compute_dtype, family.head_dim
                )
            else:
                kv_layout = None
            done0 = jnp.zeros((b,), dtype=bool)

            def decode_body(carry, t):
                cache, logits, key, done, wasted = carry
                # rows already EOS-frozen still ride the full scan — count
                # them so the serving bench can quantify what continuous
                # batching's iteration-level retirement recovers
                wasted = wasted + jnp.sum(done, dtype=jnp.int32)
                key, sub = jax.random.split(key)
                token = sample(logits, sub, temp, p_threshold)
                if eos_on:
                    token = jnp.where(done, pad_id, token)
                    done = done | (token == eos_id)
                logits, cache, *_ = decode_fn(
                    config, params, cache, token[:, None], t, kv_layout=kv_layout
                )
                return (cache, logits, key, done, wasted), token

            (_, _, _, _, wasted), new_tokens = lax.scan(
                decode_body, (cache, logits, key, done0, jnp.int32(0)),
                prompt_len + jnp.arange(max_new_tokens),
            )
            return jnp.concatenate([input_ids, new_tokens.T], axis=1), wasted

        # jit() itself is cheap (tracing happens at first call) and two
        # threads racing here just build equivalent wrappers — last insert
        # wins; only the dict mutation needs the lock
        run = jax.jit(_run)
        with cache_lock:
            jit_cache[cache_key] = run
            cache_max = getattr(model, "_generate_cache_max", _GENERATE_CACHE_MAX)
            while len(jit_cache) > cache_max:
                jit_cache.popitem(last=False)
    out, wasted = run(
        model.params, input_ids, jax.random.key(seed),
        jnp.float32(temperature if temp_on else 1.0),
        jnp.float32(top_p if top_p_on else 1.0),
        jnp.int32(eos_token_id if eos_on else -1),
        jnp.int32(pad_token_id),
    )
    # device scalar, NOT read back here — materialized lazily by
    # last_generate_stats() so generate() stays dispatch-only
    model._last_generate_wasted = wasted
    return out


def generate_cache_stats(model: Model) -> dict:
    """Observability for the per-model compiled-program LRU: how many
    executables are live and which structural keys they hold. The serving
    bench reports this to prove dynamic batching's bucket padding keeps the
    executable count bounded under varied traffic."""
    cache = getattr(model, "_generate_cache", None)
    lock = getattr(model, "_generate_cache_lock", None)
    cache_max = getattr(model, "_generate_cache_max", _GENERATE_CACHE_MAX)
    if cache is None:
        return {"size": 0, "max": cache_max, "keys": []}
    if lock is not None:
        with lock:
            keys = list(cache.keys())
    else:
        keys = list(cache.keys())
    return {"size": len(keys), "max": cache_max, "keys": keys}


def last_generate_stats(model: Model) -> dict:
    """Early-exit telemetry for the most recent ``generate()`` on this
    model: ``wasted_decode_steps`` counts (row, step) pairs where the row
    was already EOS-frozen but the fused scan still ran its decode compute.
    The counter lives on device until this accessor reads it back, so the
    generate hot path never blocks; static mode behavior is unchanged —
    this only measures what ``mode="continuous"`` recovers."""
    wasted = getattr(model, "_last_generate_wasted", None)
    if wasted is None:
        return {"wasted_decode_steps": 0}
    return {"wasted_decode_steps": int(wasted)}


def prepare_inference(model: Model, mesh=None, rules=None) -> Model:
    """Shard a model for inference over the mesh (the reference's
    ``prepare_pippy``/``dispatch_model`` role): params placed per rules, and
    the compiled forward/generate path runs SPMD."""
    from .big_modeling import dispatch_model

    return dispatch_model(model, mesh=mesh, rules=rules)
