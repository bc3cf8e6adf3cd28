"""The plain reference of the llama family (Mistral-7B here): forward, loss,
gradients and AdamW in straightforward ``jax.numpy``, float32 with matmuls at
``highest``. No kernel, no cache, no batching tricks, nothing of the program.

It follows the published description (pre-norm RMSNorm, rotary positions,
grouped-query attention under a causal sliding window, SwiGLU, untied head,
mean next-token cross entropy). Departures: rotary pairs are interleaved
(``x[2i], x[2i+1]``), which is the published model under a permutation of the
q/k columns and is how the weights made from the seed are laid out; each layer
is wrapped in ``jax.checkpoint`` so that the float32 activations fit beside
four copies of the parameters.

``precision="float8"`` computes the same mathematics one step below the bf16
that the configuration states, as the control: operands to e4m3 in the forward
pass and the incoming gradient of every matmul to e5m2 in the backward pass,
each under a per-tensor scale, as fp8 training does it.
"""

from __future__ import annotations

import functools

import jax
import numpy as np


def weight_spec(cfg: dict) -> list:
    d, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n = cfg["num_hidden_layers"]

    def dense(name, fan_in, fan_out):
        return (name, (n, fan_in, fan_out), "normal", 1.0 / np.sqrt(fan_in))

    return [
        ("embed_tokens.embedding", (v, d), "normal", 0.02),
        dense("layers.attn.q_proj.kernel", d, h * hd),
        dense("layers.attn.k_proj.kernel", d, kvh * hd),
        dense("layers.attn.v_proj.kernel", d, kvh * hd),
        dense("layers.attn.o_proj.kernel", h * hd, d),
        dense("layers.mlp.gate_proj.kernel", d, i),
        dense("layers.mlp.up_proj.kernel", d, i),
        dense("layers.mlp.down_proj.kernel", i, d),
        ("layers.input_norm.scale", (n, d), "ones", 1.0),
        ("layers.post_attn_norm.scale", (n, d), "ones", 1.0),
        ("final_norm.scale", (d,), "ones", 1.0),
        ("lm_head.kernel", (d, v), "normal", 1.0 / np.sqrt(d)),
    ]


# --------------------------------------------------------------------- precision
def _round_to(x, dtype, top):
    """Round to ``dtype`` under a per-tensor scale."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _bf16_einsum(spec, a, b):
    import jax.numpy as jnp

    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _float8_einsum(spec, a, b):
    import jax.numpy as jnp

    return _bf16_einsum(spec, _round_to(a, jnp.float8_e4m3fn, 448.0),
                        _round_to(b, jnp.float8_e4m3fn, 448.0))


def _float8_fwd(spec, a, b):
    import jax.numpy as jnp

    a8, b8 = _round_to(a, jnp.float8_e4m3fn, 448.0), _round_to(b, jnp.float8_e4m3fn, 448.0)
    return _bf16_einsum(spec, a8, b8), (a8, b8)


def _float8_bwd(spec, kept, g):
    import jax
    import jax.numpy as jnp

    _, pull = jax.vjp(lambda x, y: _bf16_einsum(spec, x, y), *kept)
    return pull(_round_to(g, jnp.float8_e5m2, 57344.0))


_float8_einsum.defvjp(_float8_fwd, _float8_bwd)


def matmul(spec: str, a, b, precision: str):
    """``einsum`` with the operands at ``precision`` and a float32 sum."""
    import jax
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "float8":
        return _float8_einsum(spec, a, b)
    raise ValueError(f"unknown precision {precision!r}")


# ----------------------------------------------------------------------- forward
def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    import jax.numpy as jnp

    _, s, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    angles = np.outer(np.arange(s), freqs)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _layer(cfg, precision, x, w):
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    mm = functools.partial(matmul, precision=precision)

    y = _rms_norm(x, w["input_norm.scale"], cfg["rms_norm_eps"])
    q = mm("bsd,de->bse", y, w["attn.q_proj.kernel"]).reshape(b, s, h, hd)
    k = mm("bsd,de->bse", y, w["attn.k_proj.kernel"]).reshape(b, s, kvh, hd)
    v = mm("bsd,de->bse", y, w["attn.v_proj.kernel"]).reshape(b, s, kvh, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    pos = np.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        seen &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    scores = jnp.where(jnp.asarray(seen)[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    x = x + mm("bse,ed->bsd", attn, w["attn.o_proj.kernel"])

    y = _rms_norm(x, w["post_attn_norm.scale"], cfg["rms_norm_eps"])
    gate = mm("bsd,di->bsi", y, w["mlp.gate_proj.kernel"])
    up = mm("bsd,di->bsi", y, w["mlp.up_proj.kernel"])
    return x + mm("bsi,id->bsd", jax.nn.silu(gate) * up, w["mlp.down_proj.kernel"])


def loss(cfg: dict, weights: dict, ids, precision: str = "float32", keep=None):
    """Mean next-token cross entropy of ``ids`` (batch, sequence). ``keep``
    (batch, sequence-1) weighs the positions: the planted half-batch fault."""
    import jax
    import jax.numpy as jnp

    x = weights["embed_tokens.embedding"][ids]
    layer = jax.checkpoint(functools.partial(_layer, cfg, precision))
    for index in range(cfg["num_hidden_layers"]):
        x = layer(x, {
            name[len("layers."):]: leaf[index]
            for name, leaf in weights.items() if name.startswith("layers.")
        })
    x = _rms_norm(x, weights["final_norm.scale"], cfg["rms_norm_eps"])
    logits = matmul("bsd,dv->bsv", x, weights["lm_head.kernel"], precision)[:, :-1]
    labels = ids[:, 1:]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0]
    if keep is None:
        return jnp.mean(nll)
    return jnp.sum(nll * keep) / jnp.sum(keep)


# ------------------------------------------------------------------------- AdamW
def make_step(cfg: dict, optimizer: dict, precision: str = "float32", keep=None):
    """One step of clipped AdamW as a jitted function of ``(p, m, v, ids, t)``
    that consumes ``p, m, v`` and returns them new, with the loss, the norm of
    each leaf of the gradient as the optimizer gets it (after clipping) and the
    gradient's norm before clipping."""
    import jax
    import jax.numpy as jnp

    lr, wd = optimizer["learning_rate"], optimizer["weight_decay"]
    b1, b2, eps = optimizer.get("b1", 0.9), optimizer.get("b2", 0.999), optimizer.get("eps", 1e-8)
    clip = optimizer.get("max_grad_norm")

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, ids, t):
        value, g = jax.value_and_grad(lambda q: loss(cfg, q, ids, precision, keep))(p)
        total = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        if clip is not None:
            factor = jnp.minimum(1.0, clip / (total + 1e-6))
            g = {k: x * factor for k, x in g.items()}
        norms = {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in g.items()}
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_m[k] = b1 * m[k] + (1 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g[k])
            m_hat = new_m[k] / (1 - b1 ** t)
            v_hat = new_v[k] / (1 - b2 ** t)
            new_p[k] = p[k] - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p[k])
        return new_p, new_m, new_v, value, norms, total

    return step


def train(cfg: dict, weights: dict, rows, optimizer: dict, precision: str = "float32",
          keep=None) -> dict:
    """``len(rows)`` steps of clipped AdamW from ``weights`` (float32, consumed).
    Returns each step's loss, the norm of each leaf of the first gradient as the
    optimizer gets it, and the weights after the last step."""
    import jax.numpy as jnp

    step = make_step(cfg, optimizer, precision, keep)
    p = weights
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, first_norms, first_total = [], None, None
    for index, ids in enumerate(rows):
        p, m, v, value, norms, total = step(p, m, v, jnp.asarray(ids), jnp.float32(index + 1))
        losses.append(float(value))
        if index == 0:
            first_norms = {k: float(x) for k, x in norms.items()}
            first_total = float(total)
    del m, v
    return {"losses": losses, "grad_norms": first_norms, "grad_norm_unclipped": first_total,
            "weights": p}
