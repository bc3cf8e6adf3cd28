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
    decode_chunk_positions,
    decode_walked_positions,
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


# ------------------------------------------------ the walk's own edges (PR 31)
# The decode kernel walks a slot's live blocks a chunk of T positions at a
# time, every head a row of one matmul. At real widths: blocks of 16, so T is
# 128 and a chunk 8 blocks; rows of 20 blocks, which 8 does not divide.
WALK_BS, WALK_BPR, WALK_LAYERS = 16, 20, 3
T = decode_chunk_positions(WALK_BS)
# (query heads, kv heads, head_dim): GPT-2 large, LFM2, and a quarter of Mistral
WALK_HEADS = {"20x20x64": (20, 20, 64), "32x8x64": (32, 8, 64), "8x2x128": (8, 2, 128)}
# what the kernel is allowed against the reference in float32 on the same
# stored values: exact arithmetic's order apart in f32; P rounded to bf16 (and
# an int8 pool's weights times their scales) otherwise
WALK_ATOL = {"f32": 2e-5, "bf16": 2e-2, "int8": 4e-2}


def _walk_case(kind, heads, positions, seed, *, vacant=(), poisoned=()):
    """Stacked pools ``(L, blocks, bs, h_kv * d)`` as the engine stores them,
    one row of blocks a slot. A slot owns the blocks that hold its positions
    ``<= pos``; ``vacant`` slots own nothing and their table is all null
    (block 0); ``poisoned`` slots own blocks of NaN. Every block no healthy
    slot owns, block 0 among them, is NaN (for int8: its scales are), and
    every table entry past a slot's live blocks points at one. Returns the
    operands and a float32 reference pool with the poison washed out."""
    h, h_kv, d = heads
    rng = np.random.default_rng(seed)
    b = len(positions)
    blocks = 1 + b * WALK_BPR
    shape = (WALK_LAYERS, blocks, WALK_BS, h_kv * d)
    tables = np.zeros((b, WALK_BPR), np.int32)
    healthy = np.zeros(blocks, bool)
    for slot, pos in enumerate(positions):
        if slot in vacant:
            continue
        n_live = pos // WALK_BS + 1
        own = 1 + slot * WALK_BPR + rng.permutation(WALK_BPR)
        tables[slot, :n_live] = own[:n_live]
        # dead entries: some other slot's row, never this one's live blocks
        tables[slot, n_live:] = 1 + ((slot + 1) % b) * WALK_BPR + rng.integers(
            0, WALK_BPR, WALK_BPR - n_live)
        if slot not in poisoned:
            healthy[own[:n_live]] = True
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.bfloat16}[kind]
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), dtype)
    if kind == "int8":
        k = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        scales = [rng.uniform(1e-3, 2e-2, size=shape[:3]).astype(np.float32) for _ in range(2)]
        for s in scales:
            s[:, ~healthy] = np.nan
        scales = tuple(jnp.asarray(s) for s in scales)
        ref = [jnp.nan_to_num(x.astype(jnp.float32) * s[..., None]) for x, s in zip((k, v), scales)]
    else:
        k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
        k[:, ~healthy] = np.nan
        v[:, ~healthy] = np.nan
        k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
        scales = None
        ref = [jnp.nan_to_num(x.astype(jnp.float32)) for x in (k, v)]
    return q, k, v, scales, jnp.asarray(tables), jnp.asarray(positions, jnp.int32), ref


def _walk_reference(q, ref, tables, pos, layer, heads):
    _, h_kv, d = heads
    kr, vr = (x[layer].reshape(x.shape[1], WALK_BS, h_kv, d) for x in ref)
    return paged_attention(q.astype(jnp.float32), kr, vr, tables, pos)


@pytest.mark.parametrize("layer", [0, 1, WALK_LAYERS - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("heads", list(WALK_HEADS))
def test_decode_walk_edges_match_reference(heads, kind, layer):
    # pos at 0, at a chunk's last position, at the next chunk's first and
    # second, at the row's last (a chunk of 4 blocks, not 8), and a vacant
    # slot with an all-null table beside that full one
    positions = [0, T - 1, T, T + 1, WALK_BPR * WALK_BS - 1, 0]
    q, k, v, scales, tables, pos, ref = _walk_case(
        kind, WALK_HEADS[heads], positions, seed=100 + layer, vacant={5})
    out = jax.jit(
        lambda layer: paged_flash_decode(
            q, k, v, tables, pos, layer=layer, interpret=True, **_scale_kwargs(scales))
    )(jnp.int32(layer))
    assert out.shape == q.shape and out.dtype == q.dtype
    live = slice(0, 5)  # the vacant slot reads the null block: poison by design here
    want = _walk_reference(q, ref, tables, pos, layer, WALK_HEADS[heads])
    _assert_close(want[live], out[live].astype(jnp.float32), atol=WALK_ATOL[kind])


@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("heads", list(WALK_HEADS))
def test_decode_never_reads_what_a_slot_does_not_own(heads, kind):
    # Slots 0 and 2 hold NaN in their own blocks (a request that diverged);
    # every block nobody healthy owns is NaN and every dead table entry points
    # at one. Slots 1 and 3 walk right after them, into buffers their chunks
    # left full of NaN: short rows, a part chunk, a chunk with dead blocks.
    positions = [2 * T + 40, 5, T + 70, T + 12]
    q, k, v, scales, tables, pos, ref = _walk_case(
        kind, WALK_HEADS[heads], positions, seed=7, poisoned={0, 2})
    out = paged_flash_decode(
        q, k, v, tables, pos, layer=jnp.int32(1), interpret=True, **_scale_kwargs(scales)
    ).astype(jnp.float32)
    want = _walk_reference(q, ref, tables, pos, 1, WALK_HEADS[heads])
    for slot in (1, 3):
        assert np.isfinite(np.asarray(out[slot])).all()
        _assert_close(want[slot], out[slot], atol=WALK_ATOL[kind])
    for slot in (0, 2):  # and the poison is real: its owners do read it
        assert np.isnan(np.asarray(out[slot])).any()


@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_decode_slot_at_a_negative_position_reads_nothing(kind):
    # No caller passes one, but the op is public: such a slot walks one chunk
    # with no live block in it, gives zeros as the walk over the grid did, and
    # still starts the next slot's first copies (first of the grid and between
    # two slots: a slot that walked no chunk would leave its successor waiting
    # on copies nobody started).
    positions = [-1, T + 1, -1, 5]
    q, k, v, scales, tables, pos, ref = _walk_case(
        kind, WALK_HEADS["32x8x64"], [max(p, 0) for p in positions], seed=11, vacant={0, 2})
    pos = jnp.asarray(positions, jnp.int32)
    out = paged_flash_decode(
        q, k, v, tables, pos, layer=jnp.int32(2), interpret=True, **_scale_kwargs(scales)
    ).astype(jnp.float32)
    want = _walk_reference(q, ref, tables, jnp.maximum(pos, 0), 2, WALK_HEADS["32x8x64"])
    for slot in (1, 3):
        _assert_close(want[slot], out[slot], atol=WALK_ATOL[kind])
    for slot in (0, 2):
        assert not np.asarray(out[slot]).any()


@pytest.mark.parametrize("block_size,chunk", [(4, 128), (16, 128), (128, 128), (256, 256)])
def test_walked_positions_are_whole_chunks(block_size, chunk):
    assert decode_chunk_positions(block_size) == chunk
    walked = [decode_walked_positions(n, block_size) for n in (0, 1, chunk, chunk + 1, 3 * chunk)]
    assert walked == [chunk, chunk, chunk, 2 * chunk, 3 * chunk]


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
