"""The plain reference of LFM2 (LiquidAI, ``lfm2_moe``): the forward pass of one
sequence to float32 logits in straightforward ``jax.numpy``, matmuls at
``highest``. No cache, no kernel, no sort, no grouped matmul, nothing of the
program.

It follows the published model. A block is ``h = x + Mixer(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; after the last block ``RMSNorm`` and the head, tied
to the token embedding. The mixer of a layer is what ``layer_types`` says:

* ``conv``: ``B, C, X = split3(u @ W_in)``, ``z = B * X``, ``c_t = sum_j w_j *
  z_{t - (taps - 1) + j}`` (depthwise, causal, zeros before the sequence),
  ``out = (C * c) @ W_out``.
* ``full_attention``: ``q``, ``k`` RMS-normed over the head with one learned scale
  shared by the heads, then RoPE, then causal softmax attention, 4 query heads a
  key-value head.

The FFN of the first ``num_dense_layers`` layers is a SwiGLU of
``intermediate_size``. Every later layer: ``s = sigmoid(x @ W_r)``, the chosen
experts are the top-k of ``s + b`` (the bias chooses only), their weights
``s_i / (sum of the chosen s + 1e-6) * routed_scaling_factor``, and the result is
the weighted sum of the chosen experts' SwiGLUs. Here every expert is computed
for every position, one expert at a time, and the weight of an expert a position
did not choose is zero.

Departures, none of which changes the model: rotary pairs are interleaved
(``x[2i], x[2i+1]``), the published layout under a permutation of the q/k
columns and how the weights made from the seed are laid out; q, k and v are three
matrices; the convolution's taps are stored ``(taps, hidden)``; the layers are
stacked by kind (``attn``, ``conv``, ``dense``, ``moe``), a layer finding its own
by its place among its kind.

The weights are those made from the seed, in bfloat16 as they are served. They
are read up to float32 a layer at a time, and an expert at a time inside
a scan, so that beside the 9.2e9 bytes of bfloat16 weights only one
expert's float32 copy is alive.

``precision="float8"`` computes the same lower, as the control: both operands of
every matmul (the router's too) rounded to e4m3 under a scale per tensor.
"""

from __future__ import annotations

import numpy as np

CONV, ATTENTION = "conv", "full_attention"


def _counts(cfg: dict) -> tuple:
    types = cfg["layer_types"]
    return (types.count(ATTENTION), types.count(CONV), cfg["num_dense_layers"],
            cfg["num_hidden_layers"] - cfg["num_dense_layers"])


# what a mixer or an expert writes into the residual stream is drawn this much
# below 1/sqrt(fan_in): see ``weight_spec``
RESIDUAL_SCALE = 0.15


def weight_spec(cfg: dict) -> list:
    """Every matrix at 1/sqrt(fan_in) (as ``reference/gpt2.py`` argues: the layers,
    not the embedding, then set the logits), the embedding at 0.02, the taps at
    1/sqrt(taps), norm scales 1; the expert bias drawn at 0.02. The published bias
    is a load balancer's: its role is to leave every expert its share, and a
    random draw can at best not unbalance. At 0.1, half the spread of the scores,
    the experts that draw low starve (84% of a layer's experts get a row in a
    decode step of 32 tokens, 5.6% up or down by draw, and the step's bytes with
    them); at 0.02 the load is what it is with no bias (98%) and the choice still
    differs from the choice by score alone on a third of the tokens.

    But for the matrices through which a mixer or an expert writes into the
    residual stream (``out_proj``, the experts' ``w2``), drawn at
    ``RESIDUAL_SCALE / sqrt(fan_in)``; the leading dense layers' ``w2`` keeps the
    full scale and so sets the stream's size. At full scale the random model is
    chaotic, and the routing makes it so: a near-tie between two experts that
    rounding decides the other way replaces an expert, which moves the stream by
    far more than rounding does and flips more choices downstream. With the
    experts' ``w2`` at full scale and only the mixers damped, bfloat16 changes the
    experts of 2% of the tokens in the first expert layer and of 51% in the
    twelfth, and the widest gap reads what float8's does; at full scale everywhere
    3% and 44%; at 0.15, 2% and 9%, and the widest gap tells bfloat16 from float8
    by a factor of 3 (PERF.md, PR 30). The price: an expert layer adds 0.045 to a
    stream of 0.6 to 0.8, and one expert left out moves the widest gap no more
    than a flipped near-tie does; the mean gap sees it
    (``drivers/serve_mean.py``)."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    i, im, e = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    taps = cfg["conv_L_cache"]
    a, c, nd, nm = _counts(cfg)

    def matrix(name, lead, fan_in, fan_out, scale=1.0):
        return (name, (*lead, fan_in, fan_out), "normal", scale / np.sqrt(fan_in))

    def ones(name, shape):
        return (name, shape, "ones", 1.0)

    return [
        ("embed_tokens.embedding", (v, d), "normal", 0.02),
        ones("embedding_norm.scale", (d,)),
        ones("attn.operator_norm.scale", (a, d)),
        matrix("attn.q_proj.kernel", (a,), d, h * hd),
        matrix("attn.k_proj.kernel", (a,), d, kvh * hd),
        matrix("attn.v_proj.kernel", (a,), d, kvh * hd),
        ones("attn.q_layernorm.scale", (a, hd)),
        ones("attn.k_layernorm.scale", (a, hd)),
        matrix("attn.out_proj.kernel", (a,), h * hd, d, RESIDUAL_SCALE),
        ones("conv.operator_norm.scale", (c, d)),
        matrix("conv.in_proj.kernel", (c,), d, 3 * d),
        ("conv.conv.kernel", (c, taps, d), "normal", 1.0 / np.sqrt(taps)),
        matrix("conv.out_proj.kernel", (c,), d, d, RESIDUAL_SCALE),
        ones("dense.ffn_norm.scale", (nd, d)),
        matrix("dense.w1.kernel", (nd,), d, i),
        matrix("dense.w3.kernel", (nd,), d, i),
        matrix("dense.w2.kernel", (nd,), i, d),
        ones("moe.ffn_norm.scale", (nm, d)),
        matrix("moe.router.kernel", (nm,), d, e),
        ("moe.expert_bias", (nm, e), "normal", 0.02),
        matrix("moe.experts.w1", (nm, e), d, im),
        matrix("moe.experts.w3", (nm, e), d, im),
        matrix("moe.experts.w2", (nm, e), im, d, RESIDUAL_SCALE),
    ]


def _float8(x):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(spec: str, a, b, precision: str):
    """``einsum`` in float32 at ``highest``; under ``float8`` of the rounded
    operands."""
    import jax
    import jax.numpy as jnp

    if precision == "float8":
        a, b = _float8(a), _float8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x`` (T, heads, head_dim), position ``t`` at row ``t``; interleaved pairs."""
    import jax.numpy as jnp

    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    angles = np.outer(np.arange(t), freqs)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _conv_mixer(cfg, precision, u, w, index):
    import jax.numpy as jnp

    def f32(name):
        return w[f"conv.{name}"][index].astype(jnp.float32)

    t = u.shape[0]
    taps = cfg["conv_L_cache"]
    gate_in, gate_out, x = jnp.split(matmul("td,de->te", u, f32("in_proj.kernel"), precision), 3, axis=-1)
    z = jnp.pad(gate_in * x, ((taps - 1, 0), (0, 0)))  # zeros before the sequence
    kernel = f32("conv.kernel")
    conv = sum(kernel[j] * z[j : j + t] for j in range(taps))
    return matmul("td,de->te", gate_out * conv, f32("out_proj.kernel"), precision)


def _attention_mixer(cfg, precision, u, w, index):
    import jax
    import jax.numpy as jnp

    def f32(name):
        return w[f"attn.{name}"][index].astype(jnp.float32)

    t = u.shape[0]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = matmul("td,de->te", u, f32("q_proj.kernel"), precision).reshape(t, h, hd)
    k = matmul("td,de->te", u, f32("k_proj.kernel"), precision).reshape(t, kvh, hd)
    v = matmul("td,de->te", u, f32("v_proj.kernel"), precision).reshape(t, kvh, hd)
    q = _rope(_rms_norm(q, f32("q_layernorm.scale"), cfg["norm_eps"]), cfg["rope_theta"])
    k = _rope(_rms_norm(k, f32("k_layernorm.scale"), cfg["norm_eps"]), cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
    scores = matmul("qhd,khd->hqk", q, k, precision) / np.sqrt(hd)
    seen = jnp.asarray(np.arange(t)[None, :] <= np.arange(t)[:, None])
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    attn = matmul("hqk,khd->qhd", probs, v, precision).reshape(t, h * hd)
    return matmul("te,ed->td", attn, f32("out_proj.kernel"), precision)


def _swiglu(precision, u, w1, w3, w2):
    import jax

    gate = jax.nn.silu(matmul("td,di->ti", u, w1, precision))
    return matmul("ti,id->td", gate * matmul("td,di->ti", u, w3, precision), w2, precision)


def routing(cfg, precision, u, router, bias):
    """``(chosen (T, k), weights (T, k))`` of every position of ``u``."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(matmul("td,de->te", u, router, precision))
    _, chosen = jax.lax.top_k(scores + bias if cfg["use_expert_bias"] else scores,
                              cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * cfg["routed_scaling_factor"]


def _experts(cfg, precision, u, w, index):
    """Every expert over every position, one expert at a time, each weighted by
    what the position's routing gives it: zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    chosen, weights = routing(
        cfg, precision, u, w["moe.router.kernel"][index].astype(jnp.float32),
        w["moe.expert_bias"][index].astype(jnp.float32),
    )

    # an expert's matrices are cut out of the whole stack inside the loop, one
    # expert at a time: a layer's slice of the stack, made outside it, is 235 MB
    # that the compiler is free to make for every layer at once
    experts = cfg["num_experts"]
    stacks = tuple(w[f"moe.experts.{name}"] for name in ("w1", "w3", "w2"))
    stacks = tuple(m.reshape(-1, *m.shape[2:]) for m in stacks)

    def one(total, number):
        w1, w3, w2 = (m[index * experts + number].astype(jnp.float32) for m in stacks)
        weight = jnp.sum(jnp.where(chosen == number, weights, 0.0), axis=-1)
        return total + weight[:, None] * _swiglu(precision, u, w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(experts))
    return total


def logits(cfg: dict, weights: dict, ids, precision: str = "float32"):
    """``(len(ids), vocab)`` float32 logits of one sequence ``ids``."""
    import jax.numpy as jnp

    w, eps = weights, cfg["norm_eps"]
    x = w["embed_tokens.embedding"][ids].astype(jnp.float32)
    seen = {CONV: 0, ATTENTION: 0}
    for layer, kind in enumerate(cfg["layer_types"]):
        index, seen[kind] = seen[kind], seen[kind] + 1
        if kind == CONV:
            u = _rms_norm(x, w["conv.operator_norm.scale"][index].astype(jnp.float32), eps)
            x = x + _conv_mixer(cfg, precision, u, w, index)
        else:
            u = _rms_norm(x, w["attn.operator_norm.scale"][index].astype(jnp.float32), eps)
            x = x + _attention_mixer(cfg, precision, u, w, index)
        if layer < cfg["num_dense_layers"]:
            u = _rms_norm(x, w["dense.ffn_norm.scale"][layer].astype(jnp.float32), eps)
            x = x + _swiglu(precision, u, *(w[f"dense.{name}.kernel"][layer].astype(jnp.float32)
                                            for name in ("w1", "w3", "w2")))
        else:
            index = layer - cfg["num_dense_layers"]
            u = _rms_norm(x, w["moe.ffn_norm.scale"][index].astype(jnp.float32), eps)
            x = x + _experts(cfg, precision, u, w, index)
    x = _rms_norm(x, w["embedding_norm.scale"].astype(jnp.float32), eps)
    return matmul("td,vd->tv", x, w["embed_tokens.embedding"].astype(jnp.float32), precision)
