"""Weight-quantized matmul Pallas kernel: bf16/f32 activations × int8 weights.

The inference hot op behind utils/quantization.py: keeping weights int8 all
the way into VMEM halves their HBM traffic vs dequantize-then-matmul, and the
per-output-channel scale folds in AFTER the MXU dot (mathematically identical
for column-wise scales). Interpret-mode capable for CPU validation.

Numerics contract (graftcheck G402/G403, docs/static_analysis.md): the
int8 dot accumulates in f32 via ``preferred_element_type`` — int8 operands
keeping a narrow result type are a hard Level 5 finding — and the
per-channel scales stay f32, applied after the accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quantized_matmul"]


def _pick(n, pref):
    b = min(pref, n)
    while n % b:
        b //= 2
    return max(b, 1)


def _qmm_kernel(x_ref, q_ref, s_ref, out_ref):
    x = x_ref[:]  # (bm, K)
    q = q_ref[:]  # (K, bn) int8
    s = s_ref[:]  # (1, bn) f32 per-output-channel scale
    # compute dtype follows the activations: f32 inputs keep full mantissa
    # (the MXU runs f32 via multi-pass); bf16 inputs take the fast path
    compute = jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16
    acc = jnp.dot(
        x.astype(compute), q.astype(compute), preferred_element_type=jnp.float32
    )
    out_ref[:] = (acc * s).astype(out_ref.dtype)


def quantized_matmul(
    x: jax.Array,
    q: jax.Array,
    scales: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``x @ (q * scales)`` with int8 ``q`` staying int8 until VMEM.

    x: (..., K); q: (K, N) int8; scales: (N,) or (1, N). Returns (..., N) in
    x.dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    *lead, k = x.shape
    kq, n = q.shape
    if kq != k:
        raise ValueError(f"Inner dims mismatch: x K={k} vs q K={kq}")
    scales = scales.reshape(1, n).astype(jnp.float32)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = _pick(m, block_m)
    bn = _pick(n, block_n)

    out = pl.pallas_call(
        _qmm_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="quant_matmul",
    )(x2, q, scales)
    return out.reshape(*lead, n)
