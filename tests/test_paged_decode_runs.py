"""`paged_flash_decode` copies a run of live blocks a few pieces at a time.

Over a latent pool, where a chunk's live blocks are at most two runs in the pool
(each block of a run the one before it plus one) the kernel moves each run with
one copy for each set bit of its length, at static power-of-two lengths; any
other chunk, and every chunk of a per-head pool, is copied a block at a time. The
tables here hold every shape a row takes: whole runs, a run that crosses a chunk's
boundary, runs that end inside a chunk (every piece length), a descending row, a
scattered one, a prefix-hit head before a run, and a vacant slot at position 0.
The TPU interpreter at tiny widths, against the references (`paged_attention` for
a per-head pool, `cache_attention` over ``pool[tables]`` for a latent one); every
block no slot holds live is NaN, so a block read that should not be is seen. The
interpreter also counts the copies the kernel starts, which are held to
`decode_copy_descriptors`, the rule the engine's counters use.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.pallas.mosaic.interpret import interpret_pallas_call
from jax.experimental.pallas import tpu as pltpu

from accelerate_tpu.ops.attention import cache_attention, paged_attention
from accelerate_tpu.ops.paged_decode import (
    decode_chunk_positions,
    decode_copy_descriptors,
    decode_copy_plan,
    paged_flash_decode,
)

BS, LAYERS, LAYER = 16, 2, 1


def _rows(bpr, chunk_blocks, seed):
    """One table row a slot and its position: ``(rows, positions)``. Slot ``s``
    owns blocks ``1 + s * bpr ...`` (a hit head borrows slot 0's first three)."""
    C = chunk_blocks
    own = [1 + s * bpr + np.arange(bpr) for s in range(8)]
    rng = np.random.default_rng(seed)
    last = bpr * BS - 1
    rows = [
        own[0],                                                    # runs, every block live
        own[1],                                                    # a run that ends one block short of a chunk
        np.r_[own[2][-3:][::-1], own[2][:-3]],                     # three loose, then a run over the boundary
        own[3][::-1],                                              # descending
        rng.permutation(own[4]),                                   # scattered
        np.r_[own[0][:3], own[5][:-3]],                            # a prefix-hit head, then a run
        np.zeros(bpr, np.int64),                                   # vacant
        own[7],                                                    # a run with one live block in its chunk
    ]
    positions = [last, (C - 1) * BS - 1, (C + 5) * BS - 1, last - 7, last, (C + 3) * BS, 0,
                 C * BS + 3]
    return np.asarray(rows, np.int32), np.asarray(positions, np.int32)


def _poisoned(shape, rows, positions, seed):
    """Stacked pools ``(LAYERS, blocks, BS, lanes)``: NaN wherever no slot holds a
    live position (the null block apart: a vacant slot reads it), and the same
    with the poison washed out, for the reference."""
    rng = np.random.default_rng(seed)
    live = np.zeros(shape[1], bool)
    live[0] = True
    for row, pos in zip(rows, positions):
        live[row[: pos // BS + 1]] = True
    pool = rng.normal(size=shape).astype(np.float32)
    pool[:, ~live] = np.nan
    return pool, np.nan_to_num(pool)


def _per_head(seed, kind):
    heads, kv_heads, d = 4, 2, 8
    C = decode_chunk_positions(BS) // BS
    bpr = 2 * C + C // 2
    rows, positions = _rows(bpr, C, seed)
    shape = (LAYERS, 1 + 8 * bpr, BS, kv_heads, d)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.normal(size=(len(rows), 1, heads, d)), jnp.float32)
    if kind == "int8":  # whole numbers in the pool, scales per position
        k = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(1e-3, 2e-2, size=shape[:3]), jnp.float32)
        vs = jnp.asarray(rng.uniform(1e-3, 2e-2, size=shape[:3]), jnp.float32)
        ops = dict(k_pool=k, v_pool=v, k_scale=ks, v_scale=vs)
        ref = paged_attention(q, k[LAYER], v[LAYER], rows, positions,
                              k_scale=ks[LAYER], v_scale=vs[LAYER])
        return q, ops, rows, positions, ref, 1e-5
    (k, k_ref), (v, v_ref) = (_poisoned(shape, rows, positions, seed + i) for i in (2, 3))
    q = q.astype(jnp.bfloat16)
    ops = dict(k_pool=jnp.asarray(k, jnp.bfloat16), v_pool=jnp.asarray(v, jnp.bfloat16))
    as_bf16 = lambda x: jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    ref = paged_attention(q.astype(jnp.float32), as_bf16(k_ref)[LAYER], as_bf16(v_ref)[LAYER],
                          rows, positions)
    return q, ops, rows, positions, ref, 2e-2


def _latent(seed):
    heads, width, value = 8, 256, 128
    C = decode_chunk_positions(BS, latent=True) // BS
    bpr = C + C // 4
    rows, positions = _rows(bpr, C, seed)
    pool, clean = _poisoned((LAYERS, 1 + 8 * bpr, BS, width), rows, positions, seed + 2)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.normal(size=(len(rows), 1, heads, width)), jnp.bfloat16)
    clean = jnp.asarray(clean, jnp.bfloat16).astype(jnp.float32)
    dense = clean[LAYER][rows].reshape(len(rows), bpr * BS, 1, width)
    ref = cache_attention(q.astype(jnp.float32), dense, dense[..., :value], positions, scale=0.1)
    ops = dict(k_pool=jnp.asarray(pool, jnp.bfloat16), v_pool=None, value_dim=value, scale=0.1)
    return q, ops, rows, positions, ref, 2e-2


CASES = {"per_head_bf16": lambda: _per_head(11, "bf16"),
         "per_head_int8": lambda: _per_head(12, "int8"),
         "latent_bf16": lambda: _latent(13)}


@pytest.mark.parametrize("case", list(CASES))
def test_walk_matches_the_reference_and_starts_the_copies_the_rule_counts(case, monkeypatch):
    """Under the TPU interpreter, which simulates each copy the kernel starts and
    awaits: the result is the reference's, and the copies started over the walk
    are the sum, over slots and the pool's leaves, of `decode_copy_descriptors`
    for the slot's row and live positions (the engine's counters' rule)."""
    q, ops, rows, positions, ref, atol = CASES[case]()
    started = []
    real = interpret_pallas_call.dma_start
    monkeypatch.setattr(interpret_pallas_call, "dma_start",
                        lambda *a, **kw: (started.append(1), real(*a, **kw))[1])
    out = jax.jit(lambda layer: paged_flash_decode(
        q, block_tables=jnp.asarray(rows), pos=jnp.asarray(positions), layer=layer,
        interpret=pltpu.InterpretParams(), **ops))(jnp.int32(LAYER))
    got = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(np.asarray(ref, np.float32), got, atol=atol)
    latent = ops["v_pool"] is None
    want = sum(decode_copy_descriptors(row, pos + 1, BS, latent)
               for row, pos in zip(rows, positions))
    assert len(started) == want * (1 if latent else 2)


def test_the_latent_rows_take_every_piece_and_the_rule_counts_them():
    C = decode_chunk_positions(BS, latent=True) // BS
    rows, positions = _rows(C + C // 4, C, 13)
    counts = [decode_copy_descriptors(row, pos + 1, BS, latent=True)
              for row, pos in zip(rows, positions)]
    assert counts == [
        2,  # 32 and 8 blocks: a piece a chunk
        5,  # 31 blocks: every piece shorter than a chunk, 16 + 8 + 4 + 2 + 1
        C + 2,  # three loose blocks and a run, a copy a block; then 5 blocks, 4 + 1
        C + C // 4,  # descending: a copy a block
        C + C // 4,  # scattered
        # the hit head and a run share the first chunk, two runs of 3 and
        # 29; then 4 blocks of the run
        2 + 4 + 1,
        1,  # vacant: block 0 at position 0
        2,  # a whole chunk, then one block
    ]
    # the plan by live blocks, of the first row: a piece a set bit of a chunk's
    assert list(decode_copy_plan(rows[0], BS, latent=True)[[0, 1, 3, C, C + 1, C + 3]]) == [
        1, 1, 2, 1, 2, 3]


def test_a_per_head_row_takes_a_copy_a_block():
    # a per-head chunk is 8 blocks of keys and 8 of values, whose copies are
    # not what its walk waits on: every row, runs or not, a copy a block
    C = decode_chunk_positions(BS) // BS
    rows, positions = _rows(2 * C + C // 2, C, 11)
    for row, pos in zip(rows, positions):
        assert decode_copy_descriptors(row, pos + 1, BS) == pos // BS + 1
    assert list(decode_copy_plan(rows[0], BS)) == [1, *range(1, 2 * C + C // 2 + 1)]
