"""The plain reference of LongCat-Flash (Meituan, ``longcat``): the forward pass of
one sequence to float32 logits in straightforward ``jax.numpy``, matmuls at
``highest``. No cache, no kernel, no absorbed product, no sort, no grouped matmul,
nothing of the program.

A layer is a *double layer* (shortcut-connected MoE, ScMoE). For its input ``x``,
every norm an RMSNorm (``rms_norm_eps``):

    h1  = x  + MLA_0(norm_in0(x))
    u   = norm_post0(h1)
    m   = MoE(u)
    h2  = h1 + FFN_0(u)
    h3  = h2 + MLA_1(norm_in1(h2))
    out = h3 + FFN_1(norm_post1(h3)) + m

then a final RMSNorm and an untied head.

* MLA: ``c_q = sqrt(d / q_lora_rank) * RMSNorm(u W_qa)`` (``mla_scale_q_lora``); ``q =
  c_q W_qb``, a head ``[q_nope | q_r]``; ``[c | k_r] = u W_kva``; ``c_kv = sqrt(d /
  kv_lora_rank) * RMSNorm(c)`` (``mla_scale_kv_lora``; ``k_r`` is not scaled);
  ``q_r`` and ``k_r`` rotated (plain RoPE, ``rope_theta``), one ``k_r`` shared by all
  heads; ``[k_nope_i | v_i] = c_kv W_kvb`` for head ``i``; ``s_i = (q_nope_i .
  k_nope_i + q_r_i . k_r) * (qk_nope + qk_rope)^-0.5``, causal softmax, ``o_i =
  softmax(s_i) v_i``, ``Attn = concat(o_i) W_o``: always up-projected, a head at a
  time.
* FFN: SwiGLU of ``ffn_hidden_size``.
* MoE: ``s = softmax(u W_r)`` over all the router's outputs (``n_routed_experts``
  routed and ``zero_expert_num`` zero-compute experts, the latter last); the
  ``moe_topk`` best of ``s + e_score_correction_bias`` are chosen; ``w = s *
  routed_scaling_factor``, not renormalised; ``MoE(u) = sum_{chosen routed e} w_e
  SwiGLU_e(u) + (sum_{chosen zero z} w_z) u`` (``zero_expert_type`` "identity").
  No shared expert.

The chip's share (the configuration's ``deployment``): the routed sum runs over the
chosen experts *held here* only (``n_routed_experts`` of them from ``program_keys
.first_expert`` on, of the ``program_keys.router_experts`` outputs the router
scores); the identity part is whole (every chip computes it for its own tokens);
embedding and head hold ``vocab_size`` rows, the slice. That partial result goes on
to the next layer, here as in the program. Every held expert is computed for every
position, one at a time, weighted by what the position's routing gives it: zero
where it was not chosen.

Departures, none of which changes the model: rotary pairs are interleaved
(``x[2i], x[2i+1]``), the published layout under a permutation of the rotary
columns and how the weights made from the seed are laid out; ``W_qb``'s columns are
kept as two matrices by what they make (every head's ``q_nope``, every head's
``q_r``), stored with their outputs on the rows, and ``W_kvb``'s likewise (``k_nope``,
``v``), stored a head at a time (``(heads, kv_lora_rank, qk_nope)`` and ``(heads,
v_head_dim, kv_lora_rank)``): the published matrices under a permutation of their
columns, laid out as A.X-K1's are (PERF.md, PR 35); each attention sublayer and each
dense FFN is a tree (``attn.<2l+i>``, ``ffn.<2l+i>``, ``i`` the sublayer of double
layer ``l``), the router, its selection bias and the experts are stacked by layer.
The checkpoint's multi-token-prediction module is not part of the forward pass and
is not here.

The weights are those made from the seed, in bfloat16 as they are served, read up
to float32 a matrix (a head, an expert) at a time. ``precision="float8"`` computes
the same lower, as the control: both operands of every matmul (the router's too)
rounded to e4m3 under a scale per tensor.
"""

from __future__ import annotations

import numpy as np

# what a branch writes into the residual stream through is drawn this much below
# 1/sqrt(fan_in): see ``weight_spec``
RESIDUAL_SCALE = 0.15


def _share(cfg: dict) -> tuple:
    """``(routed experts held, the router's outputs, the first held, zero-compute
    experts)``."""
    keys = cfg.get("program_keys", {})
    held, zero = cfg["n_routed_experts"], cfg["zero_expert_num"]
    return held, keys.get("router_experts") or held + zero, keys.get("first_expert", 0), zero


def weight_spec(cfg: dict) -> list:
    """A.X-K1's and LFM2's initialiser (PERF.md, PR 30 and 35): 0.02 for the
    embedding, 1/sqrt(fan_in) for every matrix, and ``RESIDUAL_SCALE /
    sqrt(fan_in)`` for the matrices through which a branch writes into the stream
    (every ``o_proj``, every dense FFN's ``w2`` but the first's, every expert's
    ``w2``); the first layer's first FFN keeps the full scale and sets the stream's
    size, as A.X-K1's leading dense layer does. A random router's near-ties,
    decided the other way by bfloat16, are what make a random mixture chaotic at
    full scale. The selection bias is 0 (HF's buffer starts at 0; the report's
    controller moves it in training); norm scales are 1."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    n = cfg["num_layers"]
    held, routed, _, _ = _share(cfg)
    i, im = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def matrix(name, shape, fan_in, scale=1.0):
        return (name, shape, "normal", scale / np.sqrt(fan_in))

    def ones(name, shape):
        return (name, shape, "ones", 1.0)

    def attention(j):
        a = f"attn.{j}."
        return [
            ones(a + "input_norm.scale", (d,)),
            matrix(a + "q_a.kernel", (d, rq), d),
            ones(a + "q_a_norm.scale", (rq,)),
            matrix(a + "q_b_nope.kernel", (h * nope, rq), rq),
            matrix(a + "q_b_rope.kernel", (h * rope, rq), rq),
            matrix(a + "kv_a.kernel", (d, rkv + rope), d),
            ones(a + "kv_a_norm.scale", (rkv,)),
            matrix(a + "kv_b_k.kernel", (h, rkv, nope), rkv),
            matrix(a + "kv_b_v.kernel", (h, vd, rkv), rkv),
            matrix(a + "o_proj.kernel", (h * vd, d), h * vd, RESIDUAL_SCALE),
        ]

    def ffn(j):
        f = f"ffn.{j}."
        return [
            ones(f + "norm.scale", (d,)),
            matrix(f + "w1.kernel", (d, i), d),
            matrix(f + "w3.kernel", (d, i), d),
            matrix(f + "w2.kernel", (i, d), i, 1.0 if j == 0 else RESIDUAL_SCALE),
        ]

    return [
        ("embed_tokens.embedding", (v, d), "normal", 0.02),
        ones("norm.scale", (d,)),
        matrix("lm_head.kernel", (d, v), d),
        *(leaf for j in range(2 * n) for leaf in attention(j)),
        *(leaf for j in range(2 * n) for leaf in ffn(j)),
        matrix("moe.router.kernel", (n, d, routed), d),
        ("moe.router.e_score_correction_bias", (n, routed), "zeros", 0.0),
        matrix("moe.experts.w1", (n, held, d, im), d),
        matrix("moe.experts.w3", (n, held, d, im), d),
        matrix("moe.experts.w2", (n, held, im, d), im, RESIDUAL_SCALE),
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


def _rope(cfg: dict, x):
    """``x`` (T, heads, qk_rope_head_dim), position ``t`` at row ``t``, rotated by
    ``rope_theta^(-2j/dim)``; interleaved pairs."""
    import jax.numpy as jnp

    dim = cfg["qk_rope_head_dim"]
    inv_freq = float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.outer(np.arange(x.shape[0]), inv_freq)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def latent_scales(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    return (np.sqrt(d / cfg["q_lora_rank"]) if cfg["mla_scale_q_lora"] else 1.0,
            np.sqrt(d / cfg["kv_lora_rank"]) if cfg["mla_scale_kv_lora"] else 1.0)


# --------------------------------------------------------------------- the layers
def _attention(cfg, precision, u, w, j):
    """Latent attention sublayer ``j`` over ``u`` (T, D), up-projected, a head at a
    time, so that one head's ``T x T`` scores are all that is alive."""
    import jax
    import jax.numpy as jnp

    def f32(name):
        return w[f"attn.{j}.{name}"].astype(jnp.float32)

    t = u.shape[0]
    h, rkv, eps = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_scale, kv_scale = latent_scales(cfg)
    c_q = q_scale * _rms_norm(matmul("td,de->te", u, f32("q_a.kernel"), precision),
                              f32("q_a_norm.scale"), eps)
    q_nope = matmul("te,fe->tf", c_q, f32("q_b_nope.kernel"), precision).reshape(t, h, nope)
    q_rope = _rope(cfg, matmul("te,fe->tf", c_q, f32("q_b_rope.kernel"), precision).reshape(t, h, rope))
    ckr = matmul("td,de->te", u, f32("kv_a.kernel"), precision)
    c_kv = kv_scale * _rms_norm(ckr[:, :rkv], f32("kv_a_norm.scale"), eps)
    k_rope = _rope(cfg, ckr[:, None, rkv:])[:, 0]  # (T, rope): one key for every head
    w_k, w_v = f32("kv_b_k.kernel"), f32("kv_b_v.kernel")  # (h, rkv, nope), (h, v, rkv)
    seen = jnp.asarray(np.arange(t)[None, :] <= np.arange(t)[:, None])
    scale = (nope + rope) ** -0.5

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


def _ffn(cfg, precision, u, w, j):
    import jax.numpy as jnp

    return _swiglu(precision, u, *(w[f"ffn.{j}.{name}.kernel"].astype(jnp.float32)
                                   for name in ("w1", "w3", "w2")))


def routing(cfg, precision, u, router, bias):
    """``(chosen (T, k), weights (T, k))`` of every position of ``u`` over all the
    router's outputs: softmax scores, chosen by score plus bias, weighed by score
    times ``routed_scaling_factor``."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(matmul("td,de->te", u, router, precision), axis=-1)
    _, chosen = jax.lax.top_k(scores + bias, cfg["moe_topk"])
    return chosen, jnp.take_along_axis(scores, chosen, axis=-1) * cfg["routed_scaling_factor"]


def _experts(cfg, precision, u, w, layer):
    """The held experts over every position, one expert at a time, each weighted by
    what the position's routing gives it, and the zero-compute experts' ``w * u``."""
    import jax
    import jax.numpy as jnp

    held, routed, first, zero = _share(cfg)
    chosen, weights = routing(cfg, precision, u, w["moe.router.kernel"][layer].astype(jnp.float32),
                              w["moe.router.e_score_correction_bias"][layer].astype(jnp.float32))
    # an expert's matrices are cut out of the whole stack inside the loop, one
    # expert at a time (a layer's slice, made outside it, the compiler is free to
    # make for every layer at once)
    stacks = tuple(w[f"moe.experts.{name}"] for name in ("w1", "w3", "w2"))
    stacks = tuple(m.reshape(-1, *m.shape[2:]) for m in stacks)

    def one(total, number):
        w1, w3, w2 = (m[layer * held + number].astype(jnp.float32) for m in stacks)
        weight = jnp.sum(jnp.where(chosen == first + number, weights, 0.0), axis=-1)
        return total + weight[:, None] * _swiglu(precision, u, w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    identity = jnp.sum(jnp.where(chosen >= routed - zero, weights, 0.0), axis=-1)
    return total + identity[:, None] * u


def logits(cfg: dict, weights: dict, ids, precision: str = "float32"):
    """``(len(ids), vocab_size)`` float32 logits of one sequence ``ids``, over the
    slice of the vocabulary held here."""
    import jax.numpy as jnp

    w, eps = weights, cfg["rms_norm_eps"]

    def norm(x, name):
        return _rms_norm(x, w[name].astype(jnp.float32), eps)

    x = w["embed_tokens.embedding"][ids].astype(jnp.float32)
    for layer in range(cfg["num_layers"]):
        first, second = 2 * layer, 2 * layer + 1
        h1 = x + _attention(cfg, precision, norm(x, f"attn.{first}.input_norm.scale"), w, first)
        u = norm(h1, f"ffn.{first}.norm.scale")
        m = _experts(cfg, precision, u, w, layer)
        h2 = h1 + _ffn(cfg, precision, u, w, first)
        h3 = h2 + _attention(cfg, precision, norm(h2, f"attn.{second}.input_norm.scale"), w, second)
        x = h3 + _ffn(cfg, precision, norm(h3, f"ffn.{second}.norm.scale"), w, second) + m
    x = norm(x, "norm.scale")
    return matmul("td,dv->tv", x, w["lm_head.kernel"].astype(jnp.float32), precision)
