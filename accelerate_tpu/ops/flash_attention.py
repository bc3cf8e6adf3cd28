"""Flash attention as Pallas TPU kernels (forward + custom-VJP backward).

The hot-op ownership the reference never needs (it rides torch SDPA): tiled
online-softmax attention that never materializes the (S, S) score matrix in
HBM. Layout (B, S, H, D) → kernels run per (batch·head) on (block_q, D) ×
(block_k, D) tiles living in VMEM, with the MXU doing qk^T and pv.

GQA is native: KV stays at (B·H_kv, S, D) in HBM and every q head of a
group reads the SAME kv block via the BlockSpec index map — no
``repeat_kv`` materialization (an n_rep× KV bandwidth/memory saving; the
XLA fallbacks in ops/attention.py still repeat). The dk/dv kernel
accumulates a kv head's gradient across its n_rep q heads inside VMEM by
folding the q-head loop into the innermost grid dimension.

Packed sequences are first-class: optional per-token ``segment_ids``
(B, S) mask cross-document attention inside one row — the layout the C++
padded/packed collate produces. Tokens attend only within their segment
(∧ causal). The reference has no analogue (torch SDPA has no segment
support; HF packs with cross-contamination or FlashAttention-2 varlen).

Backward uses the standard recompute formulation (Dao et al.): the forward
saves only out and the per-row logsumexp L; dq and dk/dv kernels recompute
p = exp(qk - L) per tile. Set ``interpret=True`` (or run under
``pltpu.force_tpu_interpret_mode``) to validate on CPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from .attention import NEG_INF

__all__ = ["flash_attention", "flash_attention_with_lse"]

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _dot_f32(a, b, transpose_b=False):
    """MXU-native matmul: inputs stay in their storage dtype (bf16 on the hot
    path — f32 operands run the systolic array at a fraction of peak), the
    accumulator is always f32 via ``preferred_element_type``."""
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _pick_block(s: int, preferred: int) -> int:
    b = min(preferred, s)
    while s % b != 0:
        b //= 2
    return max(b, 1)


def _mask_scores(s, i, j, q_seg, k_seg, causal, block_q, block_k, window):
    """Apply causal / sliding-window / segment visibility to a
    (block_q, block_k) score tile. ``q_seg``/``k_seg`` are (block,) int32
    rows or None; ``window`` is the Mistral convention (q attends k iff
    0 <= q_pos - k_pos < window) — the lower bound applies even with
    causal=False, so a windowed query never sees future keys."""
    if causal or window is not None:
        q_pos = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + i * block_q
        k_pos = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + j * block_k
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    if q_seg is not None:
        s = jnp.where(q_seg[:, None] == k_seg[None, :], s, NEG_INF)
    return s


def _block_visible(i, j, causal, block_q, block_k, window):
    """Grid-level pruning: whether ANY (q, k) pair in the tile is visible.
    Causal bound: the tile's lowest k_pos must not exceed its highest q_pos.
    Window bound: the tile's highest k_pos must be within the window of the
    tile's LOWEST q_pos — the bottom rows of the q block keep seeing a kv
    tile after the top rows' windows have slid past it."""
    vis = True
    hi_q = i * block_q + block_q - 1
    if causal or window is not None:
        vis = jnp.logical_and(vis, j * block_k <= hi_q) if not isinstance(vis, bool) else (j * block_k <= hi_q)
    if window is not None:
        lo_q = i * block_q
        hi_k = j * block_k + block_k - 1
        in_window = hi_k > lo_q - window  # some k in tile within some q's window
        vis = jnp.logical_and(vis, in_window) if not isinstance(vis, bool) else (vis and in_window)
    return vis


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, block_q, block_k, scale,
                segmented, window, softcap=None):
    if segmented:
        qseg_ref, kseg_ref, out_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        out_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # grid pruning: skip blocks above the causal diagonal and (with a
    # sliding window) blocks entirely below every row's window — the
    # long-sequence win: compute per row becomes O(S·window), not O(S²)
    visible = _block_visible(i, j, causal, block_q, block_k, window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0]  # (bq, d) — storage dtype straight into the MXU
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]

        s = _dot_f32(q, k, transpose_b=True) * scale  # (bq, bk), f32 acc
        if softcap is not None:  # Gemma-2 tanh capping, pre-mask
            s = softcap * jnp.tanh(s / softcap)
        q_seg = qseg_ref[0, 0] if segmented else None
        k_seg = kseg_ref[0, 0] if segmented else None
        s = _mask_scores(s, i, j, q_seg, k_seg, causal, block_q, block_k, window)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + _dot_f32(p.astype(v.dtype), v)
        m_ref[:, 0] = m_cur
        l_ref[:, 0] = l_cur

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        out_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)[:, None]).astype(out_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(jnp.maximum(l, 1e-30))


def _split_segs(segs):
    """``segs`` is one (B, 1, S) labels array for both sides or a
    (q_segs, kv_segs) pair — ring attention labels its rotating kv shard
    independently of the local q shard."""
    return segs if isinstance(segs, (tuple, list)) else (segs, segs)


def _zero_dsegs(segs):
    """float0 cotangent(s) for the integer segment-label primal(s) — the
    JAX convention for nondifferentiable int inputs."""
    if segs is None:
        return None
    return jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, dtype=jax.dtypes.float0), segs
    )


def _kv_index(b, h, h_kv):
    """Merged q index (batch·h + q_head) → merged kv index for its group."""
    n_rep = h // h_kv
    if n_rep == 1:
        return b
    return (b // h) * h_kv + (b % h) // n_rep


def _seg_index(b, h):
    """Merged q index → batch index (segments are per batch row, not head)."""
    return b // h


def _flash_fwd(q, k, v, segs, h, h_kv, causal, block_q, block_k, interpret,
               window=None, softcap=None):
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    nq = s // block_q
    nk = skv // block_k
    grid = (bh, nq, nk)
    segmented = segs is not None
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (_kv_index(b, h, h_kv), j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (_kv_index(b, h, h_kv), j, 0)),
    ]
    args = [q, k, v]
    if segmented:
        # (B, 1, S) int32; same lane-major layout trick as lse below
        qsegs, ksegs = _split_segs(segs)
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (_seg_index(b, h), 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (_seg_index(b, h), 0, j)),
        ]
        args += [qsegs, ksegs]
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale, segmented=segmented, window=window, softcap=softcap,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse rides a (bh, 1, s) layout: a (1, 1, block_q) block keeps the
            # last two dims legal for TPU tiling (dim -2 equals the array dim,
            # lanes on seq) — a flat (bh, s) block of (1, block_q) is not
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            # acc, m, l accumulators live in VMEM across the kv grid dim
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return out, lse


# ---------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   causal, block_q, block_k, scale, segmented, window,
                   softcap=None):
    if segmented:
        qseg_ref, kseg_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    visible = _block_visible(i, j, causal, block_q, block_k, window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = _dot_f32(q, k, transpose_b=True) * scale
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            s = softcap * t
        q_seg = qseg_ref[0, 0] if segmented else None
        k_seg = kseg_ref[0, 0] if segmented else None
        s = _mask_scores(s, i, j, q_seg, k_seg, causal, block_q, block_k, window)
        p = jnp.exp(s - lse[:, None])
        dp = _dot_f32(do, v, transpose_b=True)
        ds = p * (dp - delta[:, None])
        if softcap is not None:  # d/ds_raw of softcap*tanh(s_raw/softcap)
            ds = ds * (1.0 - t * t)
        dq_acc[:] = dq_acc[:] + _dot_f32(ds.astype(k.dtype), k) * scale

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    causal, block_q, block_k, scale, segmented, nq, window,
                    softcap=None):
    """Grid (B·H_kv, nk, nq·n_rep): the innermost dim walks every (q block,
    q head-in-group) pair while the dk/dv output block stays put, so a kv
    head's gradient accumulates across its whole GQA group in VMEM."""
    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    j = pl.program_id(1)  # kv block
    t = pl.program_id(2)  # (q head in group) · nq + (q block)
    nt = pl.num_programs(2)
    i = t % nq  # q row block — causal visibility depends on it, not the head

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    visible = _block_visible(i, j, causal, block_q, block_k, window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = _dot_f32(q, k, transpose_b=True) * scale  # (bq, bk)
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            s = softcap * t
        q_seg = qseg_ref[0, 0] if segmented else None
        k_seg = kseg_ref[0, 0] if segmented else None
        s = _mask_scores(s, i, j, q_seg, k_seg, causal, block_q, block_k, window)
        p = jnp.exp(s - lse[:, None])
        p_lo = p.astype(do.dtype)
        dv_acc[:] = dv_acc[:] + _dot_f32(p_lo.T, do)
        dp = _dot_f32(do, v, transpose_b=True)
        ds = p * (dp - delta[:, None])
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        dk_acc[:] = dk_acc[:] + _dot_f32(ds.astype(q.dtype).T, q) * scale

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, segs, out, lse, do, h, h_kv, causal, block_q, block_k,
               interpret, window=None, dlse=None, softcap=None):
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    skv = k.shape[1]
    bh_kv = k.shape[0]
    n_rep = h // h_kv
    scale = 1.0 / math.sqrt(d)
    segmented = segs is not None
    # (bh, 1, s): same lane-major layout as lse (see _flash_fwd out_specs)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, None, :]
    if dlse is not None:
        # lse cotangent (ring-attention LSE merge): d s_ij gains
        # + dlse_i * p_ij, which folds into the kernels as delta -= dlse
        # (ds = p * (dp - delta) everywhere below) — zero kernel changes.
        delta = delta - dlse.astype(jnp.float32)
    nq = s // block_q
    nk = skv // block_k

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (_kv_index(b, h, h_kv), j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (_kv_index(b, h, h_kv), j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
    ]
    if segmented:
        qsegs, ksegs = _split_segs(segs)
    dq_args = [q, k, v, do, lse, delta]
    if segmented:
        dq_in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (_seg_index(b, h), 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (_seg_index(b, h), 0, j)),
        ]
        dq_args += [qsegs, ksegs]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale, segmented=segmented, window=window, softcap=softcap,
        ),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_args)

    # merged q index for (kv-merged index g, inner step t): the group's
    # (t // nq)-th q head
    def q_index(g, t):
        if n_rep == 1:
            return g
        return (g // h_kv) * h + (g % h_kv) * n_rep + t // nq

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda g, j, t: (q_index(g, t), t % nq, 0)),
        pl.BlockSpec((1, block_k, d), lambda g, j, t: (g, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda g, j, t: (g, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda g, j, t: (q_index(g, t), t % nq, 0)),
        pl.BlockSpec((1, 1, block_q), lambda g, j, t: (q_index(g, t), 0, t % nq)),
        pl.BlockSpec((1, 1, block_q), lambda g, j, t: (q_index(g, t), 0, t % nq)),
    ]
    dkv_args = [q, k, v, do, lse, delta]
    if segmented:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda g, j, t: (g // h_kv, 0, t % nq)),
            pl.BlockSpec((1, 1, block_k), lambda g, j, t: (g // h_kv, 0, j)),
        ]
        dkv_args += [qsegs, ksegs]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale, segmented=segmented, nq=nq, window=window,
            softcap=softcap,
        ),
        grid=(bh_kv, nk, nq * n_rep),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda g, j, t: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, j, t: (g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_args)
    return dq, dk, dv


# ---------------------------------------------------------------- public op
def _saved_by_name(out, lse):
    """The forward kernel's two results under the names a ``jax.checkpoint``
    policy can save them by (``models/llama.py``'s "dots" does): the backward
    kernels need both, and a policy that saves only matmul outputs would run
    the forward kernel again to have them."""
    return checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, segs, h, h_kv, causal, block_q, block_k, interpret,
                window, softcap):
    out, _ = _flash_fwd(q, k, v, segs, h, h_kv, causal, block_q, block_k,
                        interpret, window, softcap)
    return out


def _flash_core_fwd(q, k, v, segs, h, h_kv, causal, block_q, block_k, interpret,
                    window, softcap):
    out, lse = _saved_by_name(*_flash_fwd(
        q, k, v, segs, h, h_kv, causal, block_q, block_k, interpret, window, softcap))
    return out, (q, k, v, segs, out, lse)


def _flash_core_bwd(h, h_kv, causal, block_q, block_k, interpret, window,
                    softcap, residuals, do):
    q, k, v, segs, out, lse = residuals
    dq, dk, dv = _flash_bwd(
        q, k, v, segs, out, lse, do, h, h_kv, causal, block_q, block_k,
        interpret, window, softcap=softcap,
    )
    return dq, dk, dv, _zero_dsegs(segs)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ------------------------------------------------- (out, lse) variant
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_core_lse(q, k, v, segs, h, h_kv, causal, block_q, block_k,
                    interpret, softcap):
    """Like :func:`_flash_core` but also returns the per-row logsumexp —
    the ring-attention building block (ops/ring_attention.py): per-step
    normalized outputs merge across the ring via their LSEs, and the VJP
    accepts an ``lse`` cotangent (the merge differentiates through it).
    ``segs`` is None or a (q_segs, kv_segs) pair of (B, 1, S*) int32.
    ``softcap`` caps scores in-kernel (Gemma-2), pre-mask, exactly like
    the non-LSE core — the LSE merge math is unchanged (capping precedes
    the softmax the stats describe)."""
    return _flash_fwd(q, k, v, segs, h, h_kv, causal, block_q, block_k,
                      interpret, None, softcap)


def _flash_core_lse_fwd(q, k, v, segs, h, h_kv, causal, block_q, block_k,
                        interpret, softcap):
    out, lse = _saved_by_name(*_flash_fwd(
        q, k, v, segs, h, h_kv, causal, block_q, block_k, interpret, None, softcap))
    return (out, lse), (q, k, v, segs, out, lse)


def _flash_core_lse_bwd(h, h_kv, causal, block_q, block_k, interpret, softcap,
                        residuals, cotangents):
    q, k, v, segs, out, lse = residuals
    do, dlse = cotangents
    dq, dk, dv = _flash_bwd(
        q, k, v, segs, out, lse, do, h, h_kv, causal, block_q, block_k,
        interpret, None, dlse=dlse, softcap=softcap,
    )
    return dq, dk, dv, _zero_dsegs(segs)


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
    softcap: Optional[float] = None,
):
    """(B, Sq, H, D) x (B, Skv, H_kv, D) flash attention returning
    ``(out (B, Sq, H, D), lse (B, H, Sq) f32)``.

    The LSE output makes per-shard results mergeable (ring attention /
    any online-softmax combination): ``(out, m=lse, l=1)`` feeds
    :func:`~accelerate_tpu.ops.attention.combine_blocks` directly, and the
    custom VJP differentiates through the merge (an ``lse`` cotangent
    shifts ``delta`` in the shared backward kernels). Unlike
    :func:`flash_attention`, q and kv sequence lengths may differ —
    ``causal`` anchors both at position 0, so ring callers pass
    ``causal=True`` only on the diagonal step.

    ``segment_ids`` (B, Sq) / ``kv_segment_ids`` (B, Skv) mask
    cross-document attention for packed sequences; the two label arrays
    are independent because a ring step's kv shard rotates while q stays
    local. Passing only ``segment_ids`` labels both sides with it."""
    b, sq, hh, d = q.shape
    h_kv = k.shape[2]
    skv = k.shape[1]
    if hh % h_kv != 0:
        raise ValueError(f"num heads {hh} not divisible by kv heads {h_kv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(skv, block_k)

    def merge(x):
        n = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * n, x.shape[1], d)

    segs = None
    if segment_ids is not None:
        ks = kv_segment_ids if kv_segment_ids is not None else segment_ids
        segs = (
            segment_ids.astype(jnp.int32)[:, None, :],
            ks.astype(jnp.int32)[:, None, :],
        )
    out, lse = _flash_core_lse(
        merge(q), merge(k), merge(v), segs, hh, h_kv, causal, block_q, block_k,
        interpret, softcap,
    )
    out = out.reshape(b, hh, sq, d).transpose(0, 2, 1, 3)
    return out, lse.reshape(b, hh, sq)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(B, S, H, D) flash attention.

    * GQA: pass k/v with fewer heads (B, S, H_kv, D), H divisible by H_kv —
      kv blocks are shared across the group in the kernel, never repeated.
    * Packed sequences: ``segment_ids`` (B, S) int32 document labels —
      attention never crosses a segment boundary (the packed-SFT layout of
      ``make_padded_collate``/csrc packing).
    * Sliding window (Mistral): ``window`` W limits each query to the last W
      keys; out-of-window kv TILES are grid-pruned, so per-row compute is
      O(S·W) instead of O(S²).
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv != 0:
        raise ValueError(f"num heads {h} not divisible by kv heads {h_kv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)

    def merge(x):
        n = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)

    segs = None
    if segment_ids is not None:
        # (B, 1, S): lane-major like lse so (1, 1, block) tiles are legal
        segs = segment_ids.astype(jnp.int32)[:, None, :]
    out = _flash_core(
        merge(q), merge(k), merge(v), segs, h, h_kv, causal, block_q, block_k,
        interpret, window, softcap,
    )
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
