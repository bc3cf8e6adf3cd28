"""Attention implementations: XLA reference, blockwise, and dispatch.

The compute core shared by models/ and the context/sequence-parallel paths.
The reference delegates attention entirely to the user's model (torch SDPA);
a TPU-native framework owns it because CP/SP reshape the attention math
itself (SURVEY §5 "Long-context").

Layouts: q/k/v are (batch, seq, heads, head_dim) — the layout that keeps the
head_dim contiguous for the MXU and makes seq the shardable dim for CP/SP.
GQA is supported via n_kv_heads < n_heads (kv repeated on the fly).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = [
    "blockwise_attention_partials",
    "dot_product_attention",
    "blockwise_attention",
    "cache_attention",
    "dispatch_attention",
    "paged_attention",
    "verify_attention",
    "repeat_kv",
    "tanh_softcap",
]


def tanh_softcap(x, cap):
    """Gemma-2 logit capping: ``cap * tanh(x / cap)``, identity when ``cap``
    is None — the ONE definition every scores/logits site shares (the Pallas
    kernel bodies inline it: they also need the tanh for the backward)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, D) → (B, S, Hkv*n_rep, D) for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


# Finite mask value: ±inf NaNs XLA autodiff through max/where when a whole
# block is masked, and magnitudes ≳1e9 NaN on TPU where exp()'s internal
# range reduction (n = round(x/ln2)) overflows int32 in the transpose pass.
# -1e6 is unreachable by any real score (|scores| ≲ 1e3 after 1/√d scaling)
# yet exp(-1e6 - m) underflows to exactly 0 on every backend.
NEG_INF = -1.0e6


def _causal_mask_bias(q_len: int, kv_len: int, q_offset: int = 0, dtype=jnp.float32):
    """Additive causal bias: 0 where kv_pos <= q_pos (+offset), NEG_INF
    otherwise. ``q_offset`` supports ring attention where the local q block
    starts at a global position > 0."""
    q_pos = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0) + q_offset
    kv_pos = lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
    return jnp.where(q_pos >= kv_pos, 0.0, NEG_INF).astype(dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    softmax_dtype=jnp.float32,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention, fully materialized scores. XLA fuses this well for
    moderate sequence lengths; use the Pallas flash kernel (ops/flash_attention)
    for long sequences on TPU. ``softcap``: Gemma-2 tanh score capping
    (softcap * tanh(scores / softcap)), applied before any masking.

    ``window`` uses the Mistral convention ``0 <= q_pos - k_pos < window``
    for every engine (dense/blockwise/flash/ring/Ulysses): the lower bound
    applies EVEN WITH ``causal=False``, so a windowed query never attends
    to future keys. There is no symmetric/two-sided window mode; pass a
    ``bias`` for bidirectional locality patterns.

    ``v`` may be narrower or wider a head than ``q`` and ``k`` (latent
    attention up-projected: keys 192, values 128): the result is as wide as
    ``v``. ``scale`` overrides ``1/sqrt(d)``."""
    b, sq, h, d = q.shape
    h_kv = k.shape[2]
    n_rep = h // h_kv
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    # GQA attends grouped: q reshaped (b, sq, h_kv, n_rep, d) so each kv
    # head broadcasts over its n_rep query heads INSIDE the einsum — K/V are
    # never physically tiled n_rep× (an n_rep× KV bandwidth/memory saving,
    # same trick as the flash kernel's head-index mapping). n_rep == 1
    # degenerates to plain MHA with a size-1 group dim.
    qg = q.reshape(b, sq, h_kv, n_rep, d)
    # G402: accumulate the QK^T dot in softmax_dtype (f32) inside the einsum —
    # an .astype() after a bf16-accumulated product keeps the bf16 rounding
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=softmax_dtype
    ) * scale
    scores = tanh_softcap(scores, softcap)
    if causal:
        mask = _causal_mask_bias(sq, sk, q_offset=q_offset - kv_offset, dtype=softmax_dtype)
        scores = scores + mask[None, None, None, :, :]
    if bias is not None:
        # callers pass bias broadcastable against (b, h, sq, sk); regroup the
        # head dim to match the (b, h_kv, n_rep, sq, sk) grouped scores
        bias = jnp.broadcast_to(bias, (b, h, sq, sk)).reshape(b, h_kv, n_rep, sq, sk)
        scores = scores + bias
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # (b, sq, sk)
        scores = jnp.where(same[:, None, None], scores, NEG_INF)
    if window is not None:
        # Mistral convention 0 <= q_pos - k_pos < window: the lower bound
        # applies even when causal=False, so windowed queries never see
        # future keys (flash/blockwise enforce the same).
        q_pos = jnp.arange(sq)[:, None] + q_offset
        k_pos = jnp.arange(sk)[None, :] + kv_offset
        diff = q_pos - k_pos
        scores = jnp.where(((diff >= 0) & (diff < window))[None, None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,  # G402: f32 PV accumulation
    ).astype(v.dtype)
    return out.reshape(b, sq, h, v.shape[-1])


def cache_attention(
    q: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    sliding: Optional[jax.Array] = None,
) -> jax.Array:
    """A window of ``W`` queries a row over a dense cache: ``q`` (B, W, h, d)
    at positions ``pos .. pos+W-1`` (``pos`` a traced scalar, the whole batch
    in lockstep, or a traced (B,) vector) over ``cache_k`` / ``cache_v``
    (B, S, h_kv, d) in which those positions are already written. Query ``j``
    attends ``k_pos <= pos + j``; ``W = 1`` is a decode step. The one
    attention every serving family's step runs when no kernel does
    (kvcache.py decides which).

    GQA attends grouped (the cache is never tiled ``h / h_kv`` times), the
    scaled query and both sums are float32 (G402), ``softcap`` is applied
    before any mask. ``window`` is the Mistral convention ``q_pos - k_pos <
    window``; a traced bool ``sliding`` applies it only where true (Gemma-2's
    alternating layers: the flag rides the layer loop). Per-(q, k) scores are
    independent dot products, so row ``j = 0`` of a window equals the
    single-query call bitwise. Masked scores hit ``NEG_INF``, which softmax
    underflows to exactly 0: unwritten or padded positions never leak.

    ``cache_v`` may be narrower than ``cache_k``, the result is as wide as it
    is: over a latent cache the values are the first columns of the keys' own
    rows (``cache_v = cache_k[..., :value_dim]``, kvcache.py), and this is the
    one attention over it too."""
    b, w, h, d = q.shape
    h_kv = cache_k.shape[2]
    dtype = q.dtype
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    qg = (q.astype(jnp.float32) * scale).reshape(b, w, h_kv, h // h_kv, d)
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, cache_k.astype(dtype),
        preferred_element_type=jnp.float32,  # G402: f32 score accumulation
    )
    scores = tanh_softcap(scores, softcap)
    k_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 4)
    q_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 3) + (
        pos if jnp.ndim(pos) == 0 else pos[:, None, None, None, None]
    )
    scores = jnp.where(k_pos <= q_pos, scores, NEG_INF)
    if window is not None:
        in_window = q_pos - k_pos < window
        if sliding is not None:
            in_window = jnp.logical_or(jnp.logical_not(sliding), in_window)
        scores = jnp.where(in_window, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", weights.astype(dtype), cache_v.astype(dtype),
        preferred_element_type=jnp.float32,  # G402: f32 PV accumulation
    )
    return out.reshape(b, w, h, cache_v.shape[-1]).astype(dtype)


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    softmax_dtype=jnp.float32,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Single-token decode attention over a paged KV pool — the reference
    semantics (and kernel contract) for the kvcache subsystem's decode path.

    Shapes, per layer:
      - ``q``:            (B, 1, h, d) — one query token per live slot
      - ``k_pool/v_pool``: (num_blocks, block_size, h_kv, d); int8 when the
        pool is quantized, in which case ``k_scale``/``v_scale``
        (num_blocks, block_size) carry per-(block, position) scales and
        dequantization happens here, after the gather
      - ``block_tables``: (B, blocks_per_row) int32 — each row's ordered
        block ids; released rows point at the null block (id 0)
      - ``pos``:          (B,) int32 — the query's position; keys strictly
        beyond it are masked

    The gather ``pool[tables]`` materializes each row's (blocks_per_row *
    block_size) context window, then attention is the exact grouped-GQA math
    of :func:`dot_product_attention` with a per-row length mask: masked
    scores hit ``NEG_INF``, softmax underflows them to exactly 0.0, and
    0 × garbage == 0 — which is why recycled/unwritten block content can
    never leak between slots (the dense↔paged bitwise-parity argument, and
    the property a fused Pallas kernel must preserve: it may skip masked
    blocks entirely, never partially weight them)."""
    b, sq, h, d = q.shape
    ctx = k_pool[block_tables]  # (B, bpr, bs, h_kv, d)

    def flat(pool_rows, scale):
        bpr, bs = pool_rows.shape[1], pool_rows.shape[2]
        x = pool_rows.reshape(b, bpr * bs, *pool_rows.shape[3:])
        if scale is not None:
            s = scale[block_tables].reshape(b, bpr * bs)
            x = x.astype(softmax_dtype) * s[:, :, None, None]
        return x

    k = flat(ctx, k_scale)
    v = flat(v_pool[block_tables], v_scale)
    sk = k.shape[1]
    h_kv = k.shape[2]
    n_rep = h // h_kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, h_kv, n_rep, d)
    # G402: accumulate the QK^T dot in softmax_dtype (f32) inside the einsum —
    # an .astype() after a bf16-accumulated product keeps the bf16 rounding
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=softmax_dtype
    ) * scale
    scores = tanh_softcap(scores, softcap)  # Gemma-2 capping, pre-mask
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    live = k_pos[None, :] <= pos[:, None]  # (B, sk)
    scores = jnp.where(live[:, None, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,  # G402: f32 PV accumulation
    ).astype(v.dtype)
    return out.reshape(b, sq, h, v.shape[-1])


def verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    softmax_dtype=jnp.float32,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Masked multi-query speculative-verify attention over a paged KV
    pool — the reference semantics (and kernel contract) for the engine's
    ``verify_step``. Identical to :func:`paged_attention` except ``q`` is a
    W-token window (B, W, h, d) whose query j sits at absolute position
    ``pos[b] + j``: the length mask becomes the windowed causal
    ``k_pos <= pos + j``, so query 0 reproduces the single-token decode
    scores bitwise (per-(q, k) score elements are independent dot products)
    and each draft token attends every earlier draft in the same window.

    The window's own K/V must already be present in the pool positions it
    attends (the model's verify layer scatter-writes them into a temporary
    view first; a fused kernel would read them from registers). Per-slot
    draft-length masking is NOT applied here — padded queries past a row's
    real draft length produce garbage rows the caller discards; their
    positions sit strictly after every valid query's causal horizon, so
    they can never contaminate valid output."""
    b, sq, h, d = q.shape
    ctx = k_pool[block_tables]  # (B, bpr, bs, h_kv, d)

    def flat(pool_rows, scale):
        bpr, bs = pool_rows.shape[1], pool_rows.shape[2]
        x = pool_rows.reshape(b, bpr * bs, *pool_rows.shape[3:])
        if scale is not None:
            s = scale[block_tables].reshape(b, bpr * bs)
            x = x.astype(softmax_dtype) * s[:, :, None, None]
        return x

    k = flat(ctx, k_scale)
    v = flat(v_pool[block_tables], v_scale)
    sk = k.shape[1]
    h_kv = k.shape[2]
    n_rep = h // h_kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, h_kv, n_rep, d)
    # G402: accumulate the QK^T dot in softmax_dtype (f32) inside the einsum —
    # an .astype() after a bf16-accumulated product keeps the bf16 rounding
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=softmax_dtype
    ) * scale
    scores = tanh_softcap(scores, softcap)  # Gemma-2 capping, pre-mask
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    q_idx = jnp.arange(sq, dtype=jnp.int32)
    live = k_pos[None, None, :] <= pos[:, None, None] + q_idx[None, :, None]
    scores = jnp.where(live[:, None, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,  # G402: f32 PV accumulation
    ).astype(v.dtype)
    return out.reshape(b, sq, h, v.shape[-1])


def _shard_map_over_batch_heads(fn, q, k):
    """Mesh-native wrapper for the Pallas flash kernel: a bare pallas_call
    cannot be auto-partitioned by GSPMD — on a multi-device mesh the
    partitioner would involuntarily REPLICATE q/k/v (gathering the whole
    batch onto every chip) before the kernel. When a mesh with active
    batch/tp axes is live (and we are not already inside a manual shard_map
    region like the ring), run the kernel under a shard_map manual over
    those axes: batch rows over the data axes, heads over tp — each chip's
    kernel invocation sees only its local (B/dp, S, H/tp, D) block, which is
    exactly the flash grid's batch*head outer dimension. Causal/window/
    segment masking are per-(batch, head) so the split changes nothing.

    Returns a callable ``wrapped(q, k, v, segment_ids)`` or None when the
    plain call is the right thing (no mesh, axes inactive, non-divisible
    heads, or already manual)."""
    from ..parallel.sharding import (
        _ACT_BATCH_AXES,
        _ACT_TP_AXIS,
        _axis_entry,
        _in_manual_region,
        current_mesh,
    )

    mesh = current_mesh()
    if mesh is None:
        return None
    if _in_manual_region():
        return None  # ring/Ulysses internals own the layout already
    batch = _axis_entry(mesh, _ACT_BATCH_AXES, q.shape[0])
    heads = _axis_entry(mesh, _ACT_TP_AXIS, q.shape[2])
    if heads is not None and _axis_entry(mesh, _ACT_TP_AXIS, k.shape[2]) is None:
        heads = None  # GQA kv heads must split the same way
    if batch is None and heads is None:
        return None

    qkv_spec = P(batch, None, heads, None)
    seg_spec = P(batch, None)

    def wrapped(q, k, v, segs):
        in_specs = [qkv_spec, qkv_spec, qkv_spec]
        args = [q, k, v]
        if segs is not None:
            in_specs.append(seg_spec)
            args.append(segs)

            def body(q, k, v, segs):
                return fn(q, k, v, segment_ids=segs)
        else:
            def body(q, k, v):
                return fn(q, k, v)

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=qkv_spec,
            check_vma=False,
        )(*args)

    return wrapped


def dispatch_attention(
    impl: str,
    q,
    k,
    v,
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_block: int = 512,
    block_q: int = 2048,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
):
    """Select the attention implementation by name — the shared entry every
    causal-LM family (llama, gpt2, ...) routes through. ``impl``: "flash" |
    "blockwise" | "xla". Flash with a shifted q block (CP/SP local shard,
    cached decode) falls back to blockwise: the Pallas kernel anchors its
    causal mask at block index 0 and would silently mis-mask."""
    if impl not in ("flash", "blockwise", "xla"):
        raise ValueError(
            f"unknown attention impl {impl!r}; expected 'flash', 'blockwise', "
            "or 'xla'"
        )
    if impl == "flash" and q_offset == 0 and causal:
        from .flash_attention import flash_attention

        fn = functools.partial(
            flash_attention, causal=True, window=window,
            softcap=softcap, block_q=block_q, block_k=kv_block,
        )
        wrapped = _shard_map_over_batch_heads(fn, q, k)
        if wrapped is not None:
            return wrapped(q, k, v, segment_ids)
        if segment_ids is not None:
            return fn(q, k, v, segment_ids=segment_ids)
        return fn(q, k, v)
    if impl in ("blockwise", "flash"):
        return blockwise_attention(
            q, k, v, causal=causal, kv_block=kv_block, q_offset=q_offset,
            segment_ids=segment_ids, window=window, softcap=softcap,
        )
    return dot_product_attention(
        q, k, v, causal=causal, q_offset=q_offset, segment_ids=segment_ids,
        window=window, softcap=softcap,
    )


def _attend_block(q, k, v, bias, softcap=None):
    """One block's contribution with running log-sum-exp stats.

    ``q`` must arrive PRE-SCALED by 1/sqrt(d) — scaling must happen outside
    the block loop both for flash-kernel convention and because a scalar
    multiply of the scores inside a scanned body miscompiles to NaN gradients
    on some TPU stacks.

    Returns (unnormalized_out, row_max, row_sumexp) for online-softmax
    combination across blocks (the flash/ring attention core). All values
    stay finite: a fully-masked block yields m=NEG_INF whose contribution is
    rescaled to exactly 0 when merged with any real block."""
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )  # G402: f32 score accumulation
    scores = tanh_softcap(scores, softcap)
    if bias is not None:
        scores = scores + bias
    m = jnp.max(scores, axis=-1)  # (b,h,q), >= NEG_INF (finite)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)  # (b,h,q)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,  # G402: f32 PV accumulation
    ).astype(v.dtype)
    return out, m, l


def combine_blocks(out_a, m_a, l_a, out_b, m_b, l_b):
    """Merge two online-softmax partial results (flash attention merge rule)."""
    m_new = jnp.maximum(m_a, m_b)
    alpha = jnp.exp(m_a - m_new)
    beta = jnp.exp(m_b - m_new)
    l_new = alpha * l_a + beta * l_b
    # out arrays are (b,q,h,d); stats are (b,h,q) → transpose factor
    a_f = jnp.swapaxes(alpha, 1, 2)[..., None]
    b_f = jnp.swapaxes(beta, 1, 2)[..., None]
    out_new = out_a * a_f.astype(out_a.dtype) + out_b * b_f.astype(out_b.dtype)
    return out_new, m_new, l_new


def finalize_blocks(out, m, l):
    """Divide by the accumulated softmax denominator."""
    denom = jnp.swapaxes(l, 1, 2)[..., None]
    return out / jnp.maximum(denom, 1e-30).astype(out.dtype)


def blockwise_attention_partials(
    q, k, v, *, causal: bool = True, kv_block: int = 512, q_offset: int = 0,
    kv_offset: int = 0, segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
):
    """Online-softmax accumulation over KV blocks, returning the UNNORMALIZED
    partials (out, m, l) for combination with other shards — the shared core
    of :func:`blockwise_attention` (one device) and each ring-attention step
    (ops/ring_attention.py, where ``q_offset``/``kv_offset`` are the shard's
    global positions). ``q`` must arrive PRE-SCALED by 1/sqrt(d) and kv
    already head-repeated (see ``_attend_block``).

    ``segment_ids`` label the q rows; ``kv_segment_ids`` (default: the same
    array) label the kv rows — ring attention passes its ROTATING kv shard's
    labels here while q labels stay local."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    num_blocks = (skv + kv_block - 1) // kv_block
    pad = num_blocks * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k = k.reshape(b, num_blocks, kv_block, h, d)
    v = v.reshape(b, num_blocks, kv_block, h, d)
    seg_blocks = None
    if segment_ids is not None:
        # padding gets segment -1 (matches no real token; the kv_pos bias
        # already excludes it — this keeps the mask construction total)
        segs = (
            kv_segment_ids if kv_segment_ids is not None else segment_ids
        ).astype(jnp.int32)
        if pad:
            segs = jnp.pad(segs, ((0, 0), (0, pad)), constant_values=-1)
        seg_blocks = segs.reshape(b, num_blocks, kv_block)

    def body(carry, blk):
        out, m, l = carry
        if segment_ids is not None:
            k_blk, v_blk, seg_blk, idx = blk
        else:
            k_blk, v_blk, idx = blk
            seg_blk = None
        kv_start = kv_offset + idx * kv_block
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, kv_block), 0) + q_offset
        kv_pos = lax.broadcasted_iota(jnp.int32, (sq, kv_block), 1) + kv_start
        bias = jnp.where(kv_pos < kv_offset + skv, 0.0, NEG_INF)
        if causal:
            bias = jnp.where(q_pos >= kv_pos, bias, NEG_INF)
        if window is not None:
            # window implies the causal lower bound (see dot_product_attention)
            diff = q_pos - kv_pos
            bias = jnp.where((diff >= 0) & (diff < window), bias, NEG_INF)
        bias = bias[None, None]
        if seg_blk is not None:
            same = segment_ids[:, :, None] == seg_blk[:, None, :]  # (b, sq, bk)
            bias = jnp.where(same[:, None], bias, NEG_INF)
        o_b, m_b, l_b = _attend_block(q, k_blk, v_blk, bias, softcap=softcap)
        return combine_blocks(out, m, l, o_b, m_b, l_b), None

    init = (
        jnp.zeros((b, sq, h, d), dtype=q.dtype),
        jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32),
        jnp.zeros((b, h, sq), dtype=jnp.float32),
    )
    k_t = jnp.moveaxis(k, 1, 0)
    v_t = jnp.moveaxis(v, 1, 0)
    # jax.checkpoint on the body is load-bearing twice over: (1) the backward
    # recomputes per-block scores instead of stacking (nb, b, h, sq, kv_block)
    # residuals (the memory guarantee this op exists for), and (2) it works
    # around an XLA TPU miscompile — differentiating the un-checkpointed scan
    # NaNs dq/dk whenever a positional bias touches the scores inside the
    # body (observed on v5e even with a numerically all-zero bias; the
    # fused transpose is at fault, not the math — a bias-free body is clean).
    xs = (k_t, v_t, jnp.arange(num_blocks))
    if seg_blocks is not None:
        xs = (k_t, v_t, jnp.moveaxis(seg_blocks, 1, 0), jnp.arange(num_blocks))
    (out, m, l), _ = lax.scan(jax.checkpoint(body), init, xs)
    return out, m, l


def blockwise_attention(
    q, k, v, *, causal: bool = True, kv_block: int = 512, q_offset: int = 0,
    segment_ids: Optional[jax.Array] = None, window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Memory-efficient attention: iterate KV blocks with online softmax —
    the same math the ring-attention CP path runs across chips
    (ops/ring_attention.py), here within one device."""
    b, sq, h, d = q.shape
    n_rep = h // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    q = q * (1.0 / math.sqrt(d))  # pre-scale (see _attend_block)
    out, m, l = blockwise_attention_partials(
        q, k, v, causal=causal, kv_block=kv_block, q_offset=q_offset,
        segment_ids=segment_ids, window=window, softcap=softcap,
    )
    return finalize_blocks(out, m, l)
