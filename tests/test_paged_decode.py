"""Paged flash-decode / fused-verify / fused-sample kernels vs reference
(interpret mode on CPU).

Edge cases pinned by the paged_attention contract: released rows point at
null block 0 and are skipped, liveness is by position (block j is dead iff
j*block_size > pos), int8 blocks dequantize from per-(block,position)
scales (all-zero scale == released block contributes exact zeros), and the
fused sampling epilogue must match the engine's _filter_logits/_sample_rows
semantics BITWISE.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.engine import _sample_rows
from accelerate_tpu.kvcache import PagedKVLayout
from accelerate_tpu.ops.attention import paged_attention, verify_attention
from accelerate_tpu.ops.paged_decode import (
    fused_sample,
    paged_flash_decode,
    paged_flash_verify,
)

B, BPR, BS, H, HKV, D, NB = 3, 4, 4, 4, 2, 8, 12


def _pools(seed=0, nb=NB):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, BS, HKV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, BS, HKV, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, size=(B, BPR)), jnp.int32)
    return q, kp, vp, tables


def _assert_close(ref, out, atol=1e-5):
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=atol)


def test_decode_matches_reference_mixed_pos():
    q, kp, vp, tables = _pools()
    # fresh slot (pos=0), mid-sequence, exactly-full table
    pos = jnp.asarray([0, 5, BPR * BS - 1], jnp.int32)
    _assert_close(
        paged_attention(q, kp, vp, tables, pos),
        paged_flash_decode(q, kp, vp, tables, pos, interpret=True),
    )


def test_decode_all_null_tables_pos0():
    # every slot released: tables full of null block 0, pos=0 — the kernel
    # must still match the reference gather (which reads block 0 row 0)
    q, kp, vp, _ = _pools(seed=1)
    tables = jnp.zeros((B, BPR), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    _assert_close(
        paged_attention(q, kp, vp, tables, pos),
        paged_flash_decode(q, kp, vp, tables, pos, interpret=True),
    )


def test_decode_single_live_block():
    q, kp, vp, _ = _pools(seed=2)
    tables = jnp.zeros((B, BPR), jnp.int32)
    tables = tables.at[:, 0].set(jnp.asarray([2, 5, 9], jnp.int32))
    pos = jnp.asarray([1, 2, BS - 1], jnp.int32)
    _assert_close(
        paged_attention(q, kp, vp, tables, pos),
        paged_flash_decode(q, kp, vp, tables, pos, interpret=True),
    )


def test_decode_exactly_full_last_block():
    q, kp, vp, tables = _pools(seed=3)
    pos = jnp.full((B,), BPR * BS - 1, jnp.int32)
    _assert_close(
        paged_attention(q, kp, vp, tables, pos),
        paged_flash_decode(q, kp, vp, tables, pos, interpret=True),
    )


def test_decode_softcap():
    q, kp, vp, tables = _pools(seed=4)
    pos = jnp.asarray([0, 5, BPR * BS - 1], jnp.int32)
    _assert_close(
        paged_attention(q, kp, vp, tables, pos, softcap=30.0),
        paged_flash_decode(q, kp, vp, tables, pos, softcap=30.0, interpret=True),
    )


def test_decode_int8_with_zero_scale_blocks():
    rng = np.random.default_rng(5)
    q, kp, vp, tables = _pools(seed=5)
    kq = jnp.asarray(rng.integers(-127, 128, size=kp.shape), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=vp.shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 2e-2, size=kp.shape[:2]), jnp.float32)
    vs = jnp.asarray(rng.uniform(1e-3, 2e-2, size=vp.shape[:2]), jnp.float32)
    # all-zero-scale block: released / never-written → exact zeros after dequant
    ks = ks.at[3].set(0.0)
    vs = vs.at[3].set(0.0)
    pos = jnp.asarray([0, 5, BPR * BS - 1], jnp.int32)
    _assert_close(
        paged_attention(q, kq, vq, tables, pos, k_scale=ks, v_scale=vs),
        paged_flash_decode(
            q, kq, vq, tables, pos, k_scale=ks, v_scale=vs, interpret=True
        ),
    )


@pytest.mark.parametrize("pos_vals", [(0, 6), (3, BPR * BS - 3)])
def test_verify_matches_window_committed_reference(pos_vals):
    # the kernel keeps the draft window in registers; the reference reads a
    # pool copy with the window scattered in at pos..pos+w-1
    b, w = 2, 3
    rng = np.random.default_rng(6)
    qw = jnp.asarray(rng.normal(size=(b, w, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, BS, HKV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, BS, HKV, D)), jnp.float32)
    # disjoint tables per row (the allocator's invariant): the reference
    # commits each row's window into a shared pool copy, so a block shared
    # between rows would corrupt the other row's history
    tables = jnp.asarray(
        1 + rng.permutation(NB - 1)[: b * BPR].reshape(b, BPR), jnp.int32
    )
    pos = jnp.asarray(pos_vals, jnp.int32)
    wk = jnp.asarray(rng.normal(size=(b, w, HKV, D)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(b, w, HKV, D)), jnp.float32)
    kp_ref, vp_ref = kp, vp
    for bb in range(b):
        for j in range(w):
            ap = int(pos[bb]) + j
            if ap >= BPR * BS:
                continue
            blk = int(tables[bb, ap // BS])
            kp_ref = kp_ref.at[blk, ap % BS].set(wk[bb, j])
            vp_ref = vp_ref.at[blk, ap % BS].set(wv[bb, j])
    _assert_close(
        verify_attention(qw, kp_ref, vp_ref, tables, pos),
        paged_flash_verify(qw, kp, vp, wk, wv, tables, pos, interpret=True),
    )


def test_fused_sample_bitwise_vs_sample_rows():
    rng = np.random.default_rng(7)
    S, V = 6, 64
    logits = jnp.asarray(rng.normal(size=(S, V)) * 3, jnp.float32)
    temp = jnp.asarray([0.0, 0.7, 1.3, 1.0, 0.5, 2.0], jnp.float32)
    top_k = jnp.asarray([0, 5, 1, V, 3, 7], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9, 0.5, 0.95, 1.0, 0.3], jnp.float32)
    for trial in range(5):
        subs = jax.random.split(jax.random.key(trial), S)
        ref = _sample_rows(logits, subs, temp, top_k, top_p)
        noise = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(subs)
        out = fused_sample(logits, noise, temp, top_k, top_p, interpret=True)
        assert np.array_equal(np.asarray(ref), np.asarray(out))


def test_fused_sample_greedy_is_raw_argmax():
    # temp=0 rows must pick the FIRST argmax of the raw logits, ignoring
    # top-k/top-p filters, exactly like _sample_rows
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    logits = logits.at[0, 10].set(50.0).at[0, 20].set(50.0)  # tie → first wins
    temp = jnp.zeros((4,), jnp.float32)
    top_k = jnp.asarray([1, 2, 3, 4], jnp.int32)
    top_p = jnp.asarray([0.3, 0.3, 0.3, 0.3], jnp.float32)
    subs = jax.random.split(jax.random.key(0), 4)
    noise = jax.vmap(lambda k: jax.random.gumbel(k, (32,), jnp.float32))(subs)
    out = fused_sample(logits, noise, temp, top_k, top_p, interpret=True)
    assert int(out[0]) == 10
    assert np.array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, axis=-1))
    )


# ------------------------------------------------- layered addressing (PR 28)
# The engine hands the kernels and PagedKVLayout's ops the whole pool
# (L, num_blocks, bs, kvh * hd) and a traced layer; each must equal, bitwise,
# the same call on that layer's slice.
LAYERS = 3


def _stacked(kind, n_rep, seed):
    """``(q, k, v, scales or None, tables)``: stacked pools with the head axes
    merged, as the engine stores them; ``kind`` is bf16 or int8."""
    rng = np.random.default_rng(seed)
    h = HKV * n_rep
    q = jnp.asarray(rng.normal(size=(B, 1, h, D)), jnp.bfloat16)
    shape = (LAYERS, NB, BS, HKV * D)
    if kind == "int8":
        k = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        scales = tuple(
            jnp.asarray(rng.uniform(1e-3, 2e-2, size=shape[:3]), jnp.float32)
            for _ in range(2)
        )
    else:
        k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        scales = None
    tables = jnp.asarray(rng.integers(1, NB, size=(B, BPR)), jnp.int32)
    return q, k, v, scales, tables


def _scale_kwargs(scales, layer=None):
    if scales is None:
        return {}
    ks, vs = scales
    return {"k_scale": ks if layer is None else ks[layer],
            "v_scale": vs if layer is None else vs[layer]}


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_decode_over_stacked_pool_equals_layer_slice(kind, n_rep, layer):
    q, k, v, scales, tables = _stacked(kind, n_rep, seed=20 + layer)
    pos = jnp.asarray([0, 5, BPR * BS - 1], jnp.int32)
    whole = jax.jit(
        lambda layer: paged_flash_decode(
            q, k, v, tables, pos, layer=layer, interpret=True, **_scale_kwargs(scales)
        )
    )(jnp.int32(layer))
    one = paged_flash_decode(
        q, k[layer], v[layer], tables, pos, interpret=True,
        **_scale_kwargs(scales, layer),
    )
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(one))
    # the slice with its head axes apart, as chip_smoke.py and the kernel
    # validation hand it over, is the same pool
    apart = paged_flash_decode(
        q, k[layer].reshape(NB, BS, HKV, D), v[layer].reshape(NB, BS, HKV, D),
        tables, pos, interpret=True, **_scale_kwargs(scales, layer),
    )
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(apart))


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_verify_over_stacked_pool_equals_layer_slice(kind, n_rep, layer):
    _, k, v, scales, tables = _stacked(kind, n_rep, seed=40 + layer)
    rng = np.random.default_rng(60 + layer)
    w, h = 3, HKV * n_rep
    qw = jnp.asarray(rng.normal(size=(B, w, h, D)), jnp.bfloat16)
    wk = jnp.asarray(rng.normal(size=(B, w, HKV, D)), jnp.bfloat16)
    wv = jnp.asarray(rng.normal(size=(B, w, HKV, D)), jnp.bfloat16)
    pos = jnp.asarray([0, 6, BPR * BS - 3], jnp.int32)
    whole = jax.jit(
        lambda layer: paged_flash_verify(
            qw, k, v, wk, wv, tables, pos, layer=layer, interpret=True,
            **_scale_kwargs(scales),
        )
    )(jnp.int32(layer))
    one = paged_flash_verify(
        qw, k[layer], v[layer], wk, wv, tables, pos, interpret=True,
        **_scale_kwargs(scales, layer),
    )
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(one))


def _layout_pool(kind, seed):
    _, k, _, scales, tables = _stacked(kind, 1, seed)
    pool = {"q": k, "s": scales[0]} if kind == "int8" else k
    layout = PagedKVLayout(tables, BS, jnp.bfloat16, D)
    return layout, pool


def _layer_of(pool, layer):
    return jax.tree_util.tree_map(lambda a: a[layer], pool)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("op", ["view", "commit", "commit_column"])
def test_layout_op_with_layer_equals_per_slice_form(op, kind, layer):
    layout, pool = _layout_pool(kind, seed=80 + layer)
    rng = np.random.default_rng(90 + layer)
    pos = jnp.asarray([0, 5, BPR * BS - 1], jnp.int32)
    traced = jnp.int32(layer)
    if op == "view":
        whole = jax.jit(layout.view)(pool, traced)
        assert whole.shape == (B, BPR * BS, HKV, D)
        _assert_trees_equal(whole, jax.jit(layout.view)(_layer_of(pool, layer)))
        return
    if op == "commit":
        new = jnp.asarray(rng.normal(size=(B, BPR * BS, HKV, D)), jnp.bfloat16)
    else:
        new = jnp.asarray(rng.normal(size=(B, 1, HKV, D)), jnp.bfloat16)
    # both under jit: XLA folds the quantizer's division the same way in each
    whole = jax.jit(getattr(layout, op))(pool, new, pos, traced)
    one = jax.jit(getattr(layout, op))(_layer_of(pool, layer), new, pos)
    _assert_trees_equal(_layer_of(whole, layer), one)
    # and no other layer was touched
    for other in set(range(LAYERS)) - {layer}:
        _assert_trees_equal(_layer_of(whole, other), _layer_of(pool, other))
