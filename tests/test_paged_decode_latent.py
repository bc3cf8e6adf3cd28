"""`paged_flash_decode` over a latent pool: the walk's edges at its own chunk.

A latent pool (``value_dim``, ``v_pool=None``) holds one row a position under
every query head, its first ``value_dim`` columns also its value; the kernel
walks it ``decode_chunk_positions(block_size, latent=True)`` positions a chunk,
four times a per-head pool's. Interpret mode at tiny widths (8 heads, rows of
256, values of 128, blocks of 16), against the reference a latent store has:
``cache_attention`` over ``pool[tables]`` with the rows' first columns as values
(``kvcache.py``'s dense path; ``tests/test_axk1.py``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import cache_attention
from accelerate_tpu.ops.paged_decode import (
    decode_chunk_positions,
    decode_walked_positions,
    paged_flash_decode,
)

HEADS, ROW, VALUE, BS, LAYERS, SCALE = 8, 256, 128, 16, 2, 0.1
T = decode_chunk_positions(BS, latent=True)
# rows of 40 blocks: a chunk and a quarter, a table that is not whole chunks
BPR = 40
LAST = BPR * BS - 1
# float32: exact arithmetic's order apart; bf16: P rounded, as the per-head walk
ATOL = {"f32": 1e-5, "bf16": 2e-2}

# (positions, vacant slots, poisoned slots, slots whose result is compared)
CASES = {
    # pos at 0, at a chunk's last position, at the next chunk's first and
    # second, at the row's last (a part chunk of 8 blocks), and a vacant slot
    # (an all-null table at position 0) beside that full one
    "edges": ([0, T - 1, T, T + 1, LAST, 0], {5}, set(), [0, 1, 2, 3, 4, 5]),
    # slots 0 and 2 hold NaN in their own blocks (a request that diverged);
    # slots 1 and 3 walk right after them, into buffers left full of NaN:
    # short rows, a part chunk, a chunk with dead blocks
    "stale": ([LAST, 5, T + 70, 40], set(), {0, 2}, [1, 3]),
}


def _latent_case(kind, positions, vacant, poisoned, seed):
    """Stacked rows ``(L, blocks, bs, ROW)`` as the engine stores a latent
    cache, one row of blocks a slot. Every block no healthy slot owns is NaN
    but the null block 0 (a vacant slot reads its first row), and every table
    entry past a slot's live blocks points at a NaN block. Returns the
    operands and a float32 reference pool with the poison washed out."""
    rng = np.random.default_rng(seed)
    b = len(positions)
    blocks = 1 + b * BPR
    tables = np.zeros((b, BPR), np.int32)
    healthy = np.zeros(blocks, bool)
    healthy[0] = True
    for slot, pos in enumerate(positions):
        if slot in vacant:
            continue
        n_live = pos // BS + 1
        own = 1 + slot * BPR + rng.permutation(BPR)
        tables[slot, :n_live] = own[:n_live]
        tables[slot, n_live:] = own[n_live:]  # never live, so never walked: NaN
        if slot not in poisoned:
            healthy[own[:n_live]] = True
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[kind]
    rows = rng.normal(size=(LAYERS, blocks, BS, ROW)).astype(np.float32)
    rows[:, ~healthy] = np.nan
    rows = jnp.asarray(rows, dtype)
    q = jnp.asarray(rng.normal(size=(b, 1, HEADS, ROW)), dtype)
    ref = jnp.nan_to_num(rows.astype(jnp.float32))
    return q, rows, jnp.asarray(tables), jnp.asarray(positions, jnp.int32), ref


@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["first", "last"])
@pytest.mark.parametrize("kind", ["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_latent_walk_edges_match_the_dense_reference(case, kind, layer):
    positions, vacant, poisoned, compared = CASES[case]
    q, rows, tables, pos, ref = _latent_case(kind, positions, vacant, poisoned, seed=3 + layer)
    out = jax.jit(
        lambda layer: paged_flash_decode(q, rows, None, tables, pos, layer=layer,
                                         value_dim=VALUE, scale=SCALE, interpret=True)
    )(jnp.int32(layer))
    assert out.shape == (len(positions), 1, HEADS, VALUE) and out.dtype == q.dtype
    dense = ref[layer][tables].reshape(len(positions), BPR * BS, 1, ROW)
    want = cache_attention(q.astype(jnp.float32), dense, dense[..., :VALUE], pos, scale=SCALE)
    got = out.astype(jnp.float32)
    for slot in compared:
        assert np.isfinite(np.asarray(got[slot])).all(), slot
        np.testing.assert_allclose(np.asarray(want[slot]), np.asarray(got[slot]), atol=ATOL[kind])
    for slot in poisoned:  # and the poison is real: its owners do read it
        assert np.isnan(np.asarray(got[slot])).any()


@pytest.mark.parametrize("latent", [False, True], ids=["per_head", "latent"])
@pytest.mark.parametrize("block_size", [4, 16, 128, 256, 1024])
def test_walked_positions_are_whole_chunks_of_the_pools_own(block_size, latent):
    # a per-head pool keeps its 128-lane chunk; a latent pool's is four times
    # that; a block wider than either is a chunk of its own
    lanes = 512 if latent else 128
    chunk = max(lanes // block_size, 1) * block_size
    assert decode_chunk_positions(block_size, latent) == chunk
    if not latent:
        assert decode_chunk_positions(block_size) == chunk
    walked = [decode_walked_positions(n, block_size, latent)
              for n in (0, 1, chunk, chunk + 1, 3 * chunk)]
    assert walked == [chunk, chunk, chunk, 2 * chunk, 3 * chunk]
