"""The plain reference of GPT-2: the forward pass of one sequence to float32
logits in straightforward ``jax.numpy``, matmuls at ``highest``. No cache, no
kernel, no batching, nothing of the program.

It follows the published model: learned positions, pre-LayerNorm blocks,
multi-head causal attention, a gelu (tanh form) MLP of four times the width, the
head tied to the token embedding. The weights are those made from the seed, in
bfloat16 as they are served, read up to float32. Departure: q, k and v are three
matrices and not one fused ``c_attn``, which is the same model.

``precision="float8"`` computes the same lower, as the control: both operands of
every matmul rounded to e4m3 under a scale per tensor.
"""

from __future__ import annotations

import numpy as np


def weight_spec(cfg: dict) -> list:
    """Every matrix is drawn at 1/sqrt(fan_in), not at GPT-2's 0.02: with the head
    tied to an embedding that dwarfs what 36 small-weight layers add, the greedy
    token of a random model is its own input, 18 logits clear of the rest (read on
    the CPU and reckoned for the large model), and no comparison of logits could
    tell any precision from another. At 1/sqrt(fan_in) the layers set the logits."""
    d, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, p = cfg["num_hidden_layers"], cfg["max_position_embeddings"]

    def dense(name, fan_in, fan_out):
        return [(f"{name}.kernel", (n, fan_in, fan_out), "normal", 1.0 / np.sqrt(fan_in)),
                (f"{name}.bias", (n, fan_out), "zeros", 0.0)]

    def norm(name, shape):
        return [(f"{name}.scale", shape, "ones", 1.0), (f"{name}.bias", shape, "zeros", 0.0)]

    return [
        ("wte.embedding", (v, d), "normal", 0.02),
        ("wpe.embedding", (p, d), "normal", 0.01),
        *norm("layers.ln_1", (n, d)),
        *dense("layers.attn.c_attn_q", d, d),
        *dense("layers.attn.c_attn_k", d, d),
        *dense("layers.attn.c_attn_v", d, d),
        *dense("layers.attn.c_proj", d, d),
        *norm("layers.ln_2", (n, d)),
        *dense("layers.mlp.c_fc", d, i),
        *dense("layers.mlp.c_proj", i, d),
        *norm("ln_f", (d,)),
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


def _layer_norm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def logits(cfg: dict, weights: dict, ids, precision: str = "float32"):
    """``(len(ids), vocab)`` float32 logits of one sequence ``ids``."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    t = ids.shape[0]
    h, hd, eps = cfg["num_attention_heads"], cfg["head_dim"], cfg["layer_norm_eps"]
    x = w["wte.embedding"][ids] + w["wpe.embedding"][:t]
    seen = jnp.asarray(np.arange(t)[None, :] <= np.arange(t)[:, None])

    def block(x, lw):
        def dense(name, y):
            return matmul("td,de->te", y, lw[f"{name}.kernel"], precision) + lw[f"{name}.bias"]

        y = _layer_norm(x, lw["ln_1.scale"], lw["ln_1.bias"], eps)
        q = dense("attn.c_attn_q", y).reshape(t, h, hd)
        k = dense("attn.c_attn_k", y).reshape(t, h, hd)
        v = dense("attn.c_attn_v", y).reshape(t, h, hd)
        scores = matmul("qhd,khd->hqk", q, k, precision) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        attn = matmul("hqk,khd->qhd", probs, v, precision).reshape(t, h * hd)
        x = x + dense("attn.c_proj", attn)
        y = _layer_norm(x, lw["ln_2.scale"], lw["ln_2.bias"], eps)
        y = jax.nn.gelu(dense("mlp.c_fc", y), approximate=True)
        return x + dense("mlp.c_proj", y), None

    # the same block for every layer, over the layers stacked on the first axis
    layers = {k[len("layers."):]: v for k, v in w.items() if k.startswith("layers.")}
    x, _ = jax.lax.scan(block, x, layers)
    x = _layer_norm(x, w["ln_f.scale"], w["ln_f.bias"], eps)
    return matmul("td,vd->tv", x, w["wte.embedding"], precision)
