"""The plain reference of A.X-K1 (SKT, ``axk1``): the forward pass of one sequence
to float32 logits in straightforward ``jax.numpy``, matmuls at ``highest``. No
cache, no kernel, no absorbed product, no sort, no grouped matmul, nothing of the
program.

Pre-norm residual blocks, ``x <- x + Attn(RMSNorm(x))``, ``x <- x +
FFN(RMSNorm(x))``, a final RMSNorm and an untied head. With ``u`` the normed input
of a layer:

* ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb``, a head ``[q_nope | q_r]``; ``[c |
  k_r] = u W_kva``; ``c_kv = RMSNorm(c)``; ``q_r`` and ``k_r`` rotated (YaRN), one
  ``k_r`` shared by all heads; ``[k_nope_i | v_i] = c_kv W_kvb`` for head ``i``;
  ``s_i = (q_nope_i . k_nope_i + q_r_i . k_r) * scale``, causal softmax, ``o_i =
  softmax(s_i) v_i``, ``Attn = concat(o_i) W_o``: always up-projected, a head at a
  time, so that one head's ``T x T`` scores are all that is alive.
* ``scale = (qk_nope + qk_rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; the factor on cos and sin is ``yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)``; the inverse frequencies are YaRN's blend
  (``inv_freq``).
* The first ``first_k_dense_replace`` layers: SwiGLU of ``intermediate_size``.
  Every later layer: ``s = sigmoid(u W_r)`` over all the router's experts; a
  group's score is the sum of its two best ``s``; the ``topk_group`` best groups
  stay; the ``num_experts_per_tok`` best ``s`` inside them are chosen; ``w = s / (sum
  s + 1e-20) * routed_scaling_factor``; ``FFN = Shared(u) + sum_j w_j E_j(u)``.

The chip's share (the configuration's ``deployment``): the sum runs over the chosen
experts *held here* only (``n_routed_experts`` of them from ``program_keys
.first_expert`` on, of the ``program_keys.router_experts`` the router scores); the
shared expert is whole; embedding and head hold ``vocab_size`` rows, the slice. That
partial result goes on to the next layer, here as in the program. Every held expert
is computed for every position, one at a time, weighted by what the position's
routing gives it: zero where it was not chosen.

Departures, none of which changes the model: rotary pairs are interleaved
(``x[2i], x[2i+1]``), the published layout under a permutation of the rotary
columns and how the weights made from the seed are laid out; ``W_qb``'s columns
are kept as two matrices by what they make (every head's ``q_nope``, every head's
``q_r``), stored with their outputs on the rows, and ``W_kvb``'s likewise (``k_nope``,
``v``), stored a head at a time (``(heads, kv_lora_rank, qk_nope)`` and ``(heads,
v_head_dim, kv_lora_rank)``): the published
matrices under a permutation of their columns, laid out so that a decode step
reads them where they lie (PERF.md, PR 35); attention's parameters are a tree a layer
(``attn.<layer>.<name>``), the dense and expert layers' are stacked by kind.

The weights are those made from the seed, in bfloat16 as they are served, read up
to float32 a layer (an expert, a head) at a time. ``precision="float8"`` computes
the same lower, as the control: both operands of every matmul (the router's too)
rounded to e4m3 under a scale per tensor.
"""

from __future__ import annotations

import math

import numpy as np

# what a branch writes into the residual stream through is drawn this much below
# 1/sqrt(fan_in): see ``weight_spec``
RESIDUAL_SCALE = 0.15


def _share(cfg: dict) -> tuple:
    """``(experts held, the router's experts, the first held)``."""
    keys = cfg.get("program_keys", {})
    held = cfg["n_routed_experts"]
    return held, keys.get("router_experts") or held, keys.get("first_expert", 0)


def weight_spec(cfg: dict) -> list:
    """The LFM2 configuration's initialiser, found the hard way there (PERF.md, PR
    30 and 34): 0.02 for the embedding, 1/sqrt(fan_in) for every matrix, and
    ``RESIDUAL_SCALE / sqrt(fan_in)`` for the matrices through which a branch
    writes into the stream (``o_proj``, every expert's and the shared expert's
    ``w2``); the dense layer's ``w2`` keeps the full scale and sets the stream's
    size. A random router's near-ties, decided the other way by bfloat16, are what
    make a random mixture chaotic at full scale. No selection bias is drawn: the
    published config has none. Norm scales are 1."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    nm = n - nd
    held, routed, _ = _share(cfg)
    i, im = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    ims = im * cfg["n_shared_experts"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def matrix(name, lead, fan_in, fan_out, scale=1.0):
        return (name, (*lead, fan_in, fan_out), "normal", scale / np.sqrt(fan_in))

    def ones(name, shape):
        return (name, shape, "ones", 1.0)

    def attention(layer):
        a = f"attn.{layer}."
        return [
            ones(a + "input_norm.scale", (d,)),
            matrix(a + "q_a.kernel", (), d, rq),
            ones(a + "q_a_norm.scale", (rq,)),
            # W_qb with its outputs on the rows, W_kvb a head at a time: see "Departures"
            (a + "q_b_nope.kernel", (h * nope, rq), "normal", 1.0 / np.sqrt(rq)),
            (a + "q_b_rope.kernel", (h * rope, rq), "normal", 1.0 / np.sqrt(rq)),
            matrix(a + "kv_a.kernel", (), d, rkv + rope),
            ones(a + "kv_a_norm.scale", (rkv,)),
            matrix(a + "kv_b_k.kernel", (h,), rkv, nope),
            (a + "kv_b_v.kernel", (h, vd, rkv), "normal", 1.0 / np.sqrt(rkv)),
            matrix(a + "o_proj.kernel", (), h * vd, d, RESIDUAL_SCALE),
        ]

    return [
        ("embed_tokens.embedding", (v, d), "normal", 0.02),
        ones("norm.scale", (d,)),
        matrix("lm_head.kernel", (), d, v),
        *(leaf for layer in range(n) for leaf in attention(layer)),
        ones("dense.ffn_norm.scale", (nd, d)),
        matrix("dense.w1.kernel", (nd,), d, i),
        matrix("dense.w3.kernel", (nd,), d, i),
        matrix("dense.w2.kernel", (nd,), i, d),
        ones("moe.ffn_norm.scale", (nm, d)),
        matrix("moe.router.kernel", (nm,), d, routed),
        matrix("moe.experts.w1", (nm, held), d, im),
        matrix("moe.experts.w3", (nm, held), d, im),
        matrix("moe.experts.w2", (nm, held), im, d, RESIDUAL_SCALE),
        matrix("moe.shared.w1.kernel", (nm,), d, ims),
        matrix("moe.shared.w3.kernel", (nm,), d, ims),
        matrix("moe.shared.w2.kernel", (nm,), ims, d, RESIDUAL_SCALE),
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


# ------------------------------------------------------------------ the rotation
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg: dict) -> np.ndarray:
    """YaRN's blend (the DeepSeek-V2/V3 convention this family's keys follow) of
    ``theta^(-2j/dim)`` and the same over ``factor``, with the linear ramp between
    the dimensions that ``beta_fast`` and ``beta_slow`` turns over the original
    positions name."""
    dim, theta, sc = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    original = sc["original_max_position_embeddings"]
    low, high = (dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
                 for turns in (sc["beta_fast"], sc["beta_slow"]))
    low, high = max(math.floor(low), 0), min(math.ceil(high), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / sc["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(cfg: dict, x):
    """``x`` (T, heads, qk_rope_head_dim), position ``t`` at row ``t``;
    interleaved pairs."""
    import jax.numpy as jnp

    sc = cfg["rope_scaling"]
    amplitude = yarn_mscale(sc["factor"], sc["mscale"]) / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    angles = np.outer(np.arange(x.shape[0]), inv_freq(cfg))
    cos = jnp.asarray(np.cos(angles) * amplitude, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles) * amplitude, jnp.float32)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


# --------------------------------------------------------------------- the layers
def _attention(cfg, precision, u, w, layer):
    import jax
    import jax.numpy as jnp

    def f32(name):
        return w[f"attn.{layer}.{name}"].astype(jnp.float32)

    t = u.shape[0]
    h, rkv, eps = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    c_q = _rms_norm(matmul("td,de->te", u, f32("q_a.kernel"), precision), f32("q_a_norm.scale"), eps)
    q_nope = matmul("te,fe->tf", c_q, f32("q_b_nope.kernel"), precision).reshape(t, h, nope)
    q_rope = _rope(cfg, matmul("te,fe->tf", c_q, f32("q_b_rope.kernel"), precision).reshape(t, h, rope))
    ckr = matmul("td,de->te", u, f32("kv_a.kernel"), precision)
    c_kv = _rms_norm(ckr[:, :rkv], f32("kv_a_norm.scale"), eps)
    k_rope = _rope(cfg, ckr[:, None, rkv:])[:, 0]  # (T, rope): one key for every head
    w_k, w_v = f32("kv_b_k.kernel"), f32("kv_b_v.kernel")  # (h, rkv, nope), (h, v, rkv)
    seen = jnp.asarray(np.arange(t)[None, :] <= np.arange(t)[:, None])
    scale = softmax_scale(cfg)

    def head(_, inputs):
        q_n, q_r, w_k_head, w_v_head = inputs  # (T, nope), (T, rope), (rkv, nope), (v, rkv)
        k_nope = matmul("tc,cn->tn", c_kv, w_k_head, precision)
        v = matmul("tc,nc->tn", c_kv, w_v_head, precision)
        scores = (matmul("qn,kn->qk", q_n, k_nope, precision)
                  + matmul("qr,kr->qk", q_r, k_rope, precision)) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, matmul("qk,kv->qv", probs, v, precision)

    _, out = jax.lax.scan(
        head, None, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2), w_k, w_v))
    attn = out.transpose(1, 0, 2).reshape(t, h * vd)
    return matmul("te,ed->td", attn, f32("o_proj.kernel"), precision)


def _swiglu(precision, u, w1, w3, w2):
    import jax

    gate = jax.nn.silu(matmul("td,di->ti", u, w1, precision))
    return matmul("ti,id->td", gate * matmul("td,di->ti", u, w3, precision), w2, precision)


def routing(cfg, precision, u, router):
    """``(chosen (T, k), weights (T, k))`` of every position of ``u``, over all
    the router's experts: the group-limited choice."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(matmul("td,de->te", u, router, precision))
    t, e = scores.shape
    groups, kept = cfg["n_group"], cfg["topk_group"]
    by_group = scores.reshape(t, groups, e // groups)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)  # its two best
    _, best = jax.lax.top_k(group_score, kept)
    stays = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], best].set(True)
    allowed = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(allowed, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * cfg["routed_scaling_factor"]


def _experts(cfg, precision, u, w, index):
    """The shared expert, whole, and the held experts over every position, one
    expert at a time, each weighted by what the position's routing gives it."""
    import jax
    import jax.numpy as jnp

    held, _, first = _share(cfg)
    chosen, weights = routing(cfg, precision, u, w["moe.router.kernel"][index].astype(jnp.float32))
    # an expert's matrices are cut out of the whole stack inside the loop, one
    # expert at a time (a layer's slice, made outside it, the compiler is free to
    # make for every layer at once)
    stacks = tuple(w[f"moe.experts.{name}"] for name in ("w1", "w3", "w2"))
    stacks = tuple(m.reshape(-1, *m.shape[2:]) for m in stacks)

    def one(total, number):
        w1, w3, w2 = (m[index * held + number].astype(jnp.float32) for m in stacks)
        weight = jnp.sum(jnp.where(chosen == first + number, weights, 0.0), axis=-1)
        return total + weight[:, None] * _swiglu(precision, u, w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    if cfg["n_shared_experts"]:
        total = total + _swiglu(precision, u, *(
            w[f"moe.shared.{name}.kernel"][index].astype(jnp.float32) for name in ("w1", "w3", "w2")))
    return total


def logits(cfg: dict, weights: dict, ids, precision: str = "float32"):
    """``(len(ids), vocab_size)`` float32 logits of one sequence ``ids``, over the
    slice of the vocabulary held here."""
    import jax.numpy as jnp

    w, eps = weights, cfg["rms_norm_eps"]
    x = w["embed_tokens.embedding"][ids].astype(jnp.float32)
    for layer in range(cfg["num_hidden_layers"]):
        u = _rms_norm(x, w[f"attn.{layer}.input_norm.scale"].astype(jnp.float32), eps)
        x = x + _attention(cfg, precision, u, w, layer)
        if layer < cfg["first_k_dense_replace"]:
            u = _rms_norm(x, w["dense.ffn_norm.scale"][layer].astype(jnp.float32), eps)
            x = x + _swiglu(precision, u, *(w[f"dense.{name}.kernel"][layer].astype(jnp.float32)
                                            for name in ("w1", "w3", "w2")))
        else:
            index = layer - cfg["first_k_dense_replace"]
            u = _rms_norm(x, w["moe.ffn_norm.scale"][index].astype(jnp.float32), eps)
            x = x + _experts(cfg, precision, u, w, index)
    x = _rms_norm(x, w["norm.scale"].astype(jnp.float32), eps)
    return matmul("td,dv->tv", x, w["lm_head.kernel"].astype(jnp.float32), precision)
