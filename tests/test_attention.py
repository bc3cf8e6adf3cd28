import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import blockwise_attention, dot_product_attention


def _qkv(b=2, s=64, h=4, kvh=None, d=16, seed=0):
    rng = np.random.default_rng(seed)
    kvh = kvh or h
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dtype=jnp.float32)
    return q, k, v


def test_blockwise_matches_reference_causal():
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=True)
    blk = blockwise_attention(q, k, v, causal=True, kv_block=16)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=1e-5)


def test_blockwise_matches_reference_noncausal():
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=False)
    blk = blockwise_attention(q, k, v, causal=False, kv_block=24)  # uneven blocks
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=1e-5)


def test_gqa_repeat():
    q, k, v = _qkv(h=8, kvh=2)
    ref = dot_product_attention(q, k, v, causal=True)
    blk = blockwise_attention(q, k, v, causal=True, kv_block=32)
    assert ref.shape == q.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(blk), atol=1e-5)


def test_blockwise_gradients_finite_with_masked_blocks():
    """Multi-block causal: later KV blocks are fully masked for early q rows —
    the configuration that NaN'd with ±inf masking; grads must stay finite."""
    q, k, v = _qkv(s=64)

    def loss(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True, kv_block=16) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_q_offset_zero_block_fully_masked_grads():
    """Ring case: a q block at offset 0 attending a KV block entirely in its
    future — everything masked; output 0-ish and grads finite."""
    q, k, v = _qkv(s=16)

    def loss(q, k, v):
        out = blockwise_attention(q, k, v, causal=True, kv_block=16, q_offset=0)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(q, k, v)
    assert np.all(np.isfinite(np.asarray(g)))


def test_q_offset_ring_semantics():
    """q_offset shifts causal masking as if the q block sat at a later global
    position — the ring-attention contract."""
    q, k, v = _qkv(s=32)
    # full sequence of 64: build from two 32-blocks
    q2, k2, v2 = _qkv(s=32, seed=1)
    qf = jnp.concatenate([q, q2], axis=1)
    kf = jnp.concatenate([k, k2], axis=1)
    vf = jnp.concatenate([v, v2], axis=1)
    ref = dot_product_attention(qf, kf, vf, causal=True)
    # second q block attends to all of kf with offset 32
    out2 = dot_product_attention(q2, kf, vf, causal=True, q_offset=32)
    np.testing.assert_allclose(np.asarray(ref[:, 32:]), np.asarray(out2), atol=1e-5)


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("per_row_pos", [False, True])
@pytest.mark.parametrize("kvh", [4, 2])
def test_cache_attention_matches_rows_of_the_full_forward(kvh, per_row_pos, w):
    """The one attention over a dense cache (the serving steps' seam,
    kvcache.py) against rows ``pos .. pos+W-1`` of the full causal forward:
    grouped and ungrouped, the whole batch at one position or each row at its
    own, a decode step (W = 1) and a verify window. What lies past the window
    in the cache is garbage a real cache holds too, and must not leak. With
    softcap and a sliding window besides, and the traced flag that turns the
    window off for a layer."""
    from accelerate_tpu.ops.attention import cache_attention, dispatch_attention

    q, k, v = _qkv(s=24, kvh=kvh)
    starts = [7, 13] if per_row_pos else [9, 9]
    pos = jnp.asarray(starts, jnp.int32) if per_row_pos else jnp.int32(9)
    rows = np.asarray(starts)[:, None] + np.arange(w)[None, :]  # (B, W)
    at = np.arange(2)[:, None], rows
    noise = jnp.asarray(np.random.default_rng(1).normal(size=k.shape), jnp.float32)
    written = (jnp.arange(24)[None, :] < jnp.asarray(starts)[:, None] + w)[:, :, None, None]
    cache_k, cache_v = jnp.where(written, k, noise), jnp.where(written, v, noise)
    for kw in ({}, {"softcap": 5.0}, {"window": 6}):
        want = np.asarray(dispatch_attention("xla", q, k, v, causal=True, **kw))[at]
        got = cache_attention(q[at], cache_k, cache_v, pos, **kw)
        assert got.shape == (2, w, 4, 16)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, err_msg=str(kw))
    everywhere = cache_attention(q[at], cache_k, cache_v, pos, window=6, sliding=jnp.bool_(False))
    plain = cache_attention(q[at], cache_k, cache_v, pos)
    np.testing.assert_array_equal(np.asarray(everywhere), np.asarray(plain))
