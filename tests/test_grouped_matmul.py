"""``ops/grouped_matmul.py``: the ``moe_gmm`` kernel, interpreted on the CPU at small
widths, against ``jax.lax.ragged_dot``.

The CPU sums a dot product in an order that depends on the matmul's shape, so
two correct float32 results differ in their last bit. Where a case asks for
equality to the bit its operands are small whole numbers, whose products and
sums float32 holds exactly in any order: then a wrong row, group, mask or tile
changes bits and rounding cannot. The same cases run on drawn normal operands
to 1e-6 of the result's largest value.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops import grouped_matmul as gm
from accelerate_tpu.ops.grouped_matmul import group_visits, grouped_matmul


def oracle(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                              preferred_element_type=jnp.float32)


def operands(rows, k, n, groups, dtype, whole, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    if whole:
        return (jax.random.randint(ka, (rows, k), -3, 4).astype(dtype),
                jax.random.randint(kb, (groups, k, n), -3, 4).astype(dtype))
    return jax.random.normal(ka, (rows, k), dtype), jax.random.normal(kb, (groups, k, n), dtype)


def agree(got, want, whole):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.float32 and got.shape == want.shape
    if whole:
        np.testing.assert_array_equal(got, want)
    else:
        assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


# rows, K, N, the groups' sizes. A row tile is 128 rows, or the rows whole where
# they are fewer (`_row_tile`)
SHAPES = {
    "uneven_groups": (128, 64, 256, [4, 1, 3, 10, 2, 7, 50, 1, 50]),
    "empty_groups_first_last_and_between": (128, 64, 128, [0, 0, 30, 0, 0, 60, 38, 0]),
    "every_group_empty": (64, 64, 128, [0, 0, 0]),
    "one_group_holds_every_row": (96, 64, 128, [0, 96, 0]),
    "a_group_spans_several_row_tiles": (512, 64, 128, [10, 300, 2, 200]),
    "a_group_holds_whole_row_tiles": (1100, 64, 128, [100, 700, 0, 300]),
    "several_groups_inside_one_row_tile": (384, 64, 128, [128, 1, 1, 1, 125, 100, 28]),
    "groups_end_on_tile_edges": (384, 64, 128, [128, 0, 128, 128]),
    "rows_past_the_last_group": (128, 64, 128, [5, 0, 40]),
    "whole_row_tiles_past_the_last_group": (640, 64, 128, [100, 30]),
    "rows_not_a_multiple_of_the_row_tile": (300, 64, 128, [100, 0, 150, 50]),
    "rows_not_a_multiple_of_sixteen": (37, 64, 128, [7, 30]),
    "rows_past_the_last_group_and_ragged_end": (700, 128, 128, [300, 1, 0, 150]),
    "width_no_multiple_of_128": (64, 48, 96, [20, 44]),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("whole", [True, False], ids=["to_the_bit", "drawn"])
@pytest.mark.parametrize("case", list(SHAPES))
def test_kernel_equals_ragged_dot(case, whole, dtype):
    rows, k, n, sizes = SHAPES[case]
    lhs, rhs = operands(rows, k, n, len(sizes), dtype, whole)
    got = jax.jit(grouped_matmul)(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    agree(got, oracle(lhs, rhs, sizes), whole)
    # rows of no group are written, as zeros
    assert not np.asarray(got)[sum(sizes):].any()


@pytest.mark.parametrize("whole", [True, False], ids=["to_the_bit", "drawn"])
@pytest.mark.parametrize("case", ["uneven_groups", "a_group_spans_several_row_tiles",
                                  "rows_not_a_multiple_of_the_row_tile"])
def test_k_in_more_than_one_tile(monkeypatch, case, whole):
    rows, _, n, sizes = SHAPES[case]
    k = 384
    # room for a (128, 128) bf16 tile of rhs and its second buffer, no more
    monkeypatch.setattr(gm, "_RHS_TILES_BYTES", 2 * 128 * 128 * 2)
    monkeypatch.setattr(gm, "_NARROWEST", 128)
    assert gm._tiles(rows, k, n, 2)[1:] == (128, 128)
    lhs, rhs = operands(rows, k, n, len(sizes), jnp.bfloat16, whole)
    agree(grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32)), oracle(lhs, rhs, sizes), whole)


LAYERS, GROUPS = 3, 6


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("rows,sizes", [(64, [4, 0, 20, 1, 30, 9]), (300, [100, 0, 0, 120, 30, 2])],
                         ids=["one_row_tile", "three_row_tiles"])
def test_stacked_groups_at_a_traced_layer(rows, sizes, layer):
    """``L x G`` groups of which one layer's hold rows: handed the layer's sizes
    and where its groups start, or every group's sizes; the layer traced."""
    lhs, rhs = operands(rows, 64, 128, LAYERS * GROUPS, jnp.bfloat16, whole=True, seed=layer)
    sizes = jnp.asarray(sizes, jnp.int32)
    every = np.zeros(LAYERS * GROUPS, np.int32)
    every[layer * GROUPS:(layer + 1) * GROUPS] = sizes
    want = oracle(lhs, rhs, every)

    @jax.jit
    def by_layer(lhs, rhs, sizes, layer):
        visits = group_visits(sizes, rows, layer * GROUPS)
        return grouped_matmul(lhs, rhs, visits), visits.count

    got, count = by_layer(lhs, rhs, sizes, jnp.int32(layer))
    agree(got, want, whole=True)
    agree(jax.jit(grouped_matmul)(lhs, rhs, jnp.asarray(every)), want, whole=True)
    # a layer's visits do not grow with the layers around it
    assert int(count) == int(group_visits(jnp.asarray(every), rows).count)


def visits_by_hand(sizes, rows, tm):
    """(group, row tile, first row, last row + 1) of every visit, groups in order,
    a group's tiles in order; then the tiles of the rows no group holds."""
    ends = np.cumsum(sizes)
    out = []
    for group, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        out += [(group, t, lo, hi) for t in range(lo // tm, -(-hi // tm))] if hi > lo else []
    padded = -(-rows // tm) * tm
    total = int(ends[-1])
    out += [(None, t, 0, 0) for t in range(total // tm, padded // tm)] if padded > total else []
    return out


@pytest.mark.parametrize("case", list(SHAPES))
def test_visits_skip_empty_groups_and_cover_every_tile(case):
    rows, _, _, sizes = SHAPES[case]
    tm = gm._row_tile(rows)
    visits = group_visits(jnp.asarray(sizes, jnp.int32), rows, first_group=7)
    want = visits_by_hand(np.asarray(sizes), rows, tm)
    count = int(visits.count)
    assert count == len(want) <= visits.group.shape[0]
    got = list(zip(*(np.asarray(a)[:count].tolist() for a in (visits.group, visits.tile, visits.lo, visits.hi))))
    held = [g for g, size in enumerate(sizes) if size]
    for (group, tile, lo, hi), (want_group, *rest) in zip(got, want):
        assert (tile, lo, hi) == tuple(rest)
        # a visit for the rows of no group names the matrix the visit before it read
        assert group - 7 == (want_group if want_group is not None else (held[-1] if held else 0))
    assert sorted({tile for _, tile, _, _ in got}) == list(range(-(-rows // tm)))


def test_three_matmuls_share_one_set_of_visits():
    rows, k, n, sizes = SHAPES["several_groups_inside_one_row_tile"]
    lhs, up = operands(rows, k, n, len(sizes), jnp.bfloat16, whole=False)
    _, down = operands(rows, n, k, len(sizes), jnp.bfloat16, whole=False, seed=1)

    @jax.jit
    def layer(lhs, up, down, sizes):
        visits = group_visits(sizes, rows)
        hidden = grouped_matmul(lhs, up, visits).astype(jnp.bfloat16)
        return grouped_matmul(hidden, down, visits)

    hidden = oracle(lhs, up, sizes).astype(jnp.bfloat16)
    agree(layer(lhs, up, down, jnp.asarray(sizes, jnp.int32)), oracle(hidden, down, sizes), whole=False)


@pytest.mark.parametrize("case", ["uneven_groups", "rows_past_the_last_group",
                                  "rows_not_a_multiple_of_the_row_tile"])
def test_gradient_is_the_oracles(case):
    rows, k, n, sizes = SHAPES[case]
    lhs, rhs = operands(rows, k, n, len(sizes), jnp.float32, whole=True)
    weight = jax.random.randint(jax.random.PRNGKey(5), (rows, n), -2, 3).astype(jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(fn):
        return lambda lhs, rhs: jnp.sum(fn(lhs, rhs, sizes) * weight)

    got = jax.jit(jax.grad(loss(grouped_matmul), argnums=(0, 1)))(lhs, rhs)
    want = jax.grad(loss(oracle), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.asarray(got[0])[int(sizes.sum()):].any()  # rows of no group


def test_gradient_through_a_stack_at_a_traced_layer():
    rows, sizes, layer = 64, jnp.asarray([4, 0, 20, 1, 30, 9], jnp.int32), 1
    lhs, rhs = operands(rows, 64, 128, LAYERS * GROUPS, jnp.float32, whole=True)
    every = jnp.zeros(LAYERS * GROUPS, jnp.int32).at[layer * GROUPS:(layer + 1) * GROUPS].set(sizes)

    @jax.jit
    def grads(lhs, rhs, layer):
        return jax.grad(lambda a, b: jnp.sum(
            grouped_matmul(a, b, group_visits(sizes, rows, layer * GROUPS)) ** 2), argnums=(0, 1))(lhs, rhs)

    got = grads(lhs, rhs, jnp.int32(layer))
    want = jax.grad(lambda a, b: jnp.sum(oracle(a, b, every) ** 2), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.asarray(got[1])[:GROUPS].any() and np.asarray(got[1])[GROUPS:2 * GROUPS].any()


@pytest.mark.parametrize("rows,k,n,want", [
    (128, 2048, 1792, (128, 2048, 1792)),   # LFM2's decode step, 32 slots x top-4: up, gate
    (128, 1792, 2048, (128, 1792, 2048)),   # and down
    (2048, 2048, 1792, (128, 2048, 1792)),  # a 512 bucket's prefill
    (2048, 1792, 2048, (128, 1792, 2048)),
    (640, 2048, 1792, (128, 2048, 1792)),   # a verify window of five
    (8, 2048, 1792, (16, 2048, 1792)),      # two slots
    (128, 4096, 14336, (128, 4096, 1024)),  # Mixtral's up: N in fourteen tiles
    (128, 14336, 4096, (128, 7168, 512)),   # and down: K whole would leave 256 columns
    (64, 48, 96, (64, 48, 96)),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_tiles_follow_from_the_shapes(rows, k, n, want):
    tm, tk, tn = gm._tiles(rows, k, n, 2)
    assert (tm, tk, tn) == want
    assert k % tk == 0 and n % tn == 0 and 2 * tk * tn * 2 <= gm._RHS_TILES_BYTES


def test_refuses_what_it_was_not_made_for():
    lhs, rhs = operands(64, 64, 128, 3, jnp.bfloat16, whole=True)
    sizes = jnp.asarray([10, 20, 30], jnp.int32)
    with pytest.raises(ValueError, match="one dtype"):
        grouped_matmul(lhs, rhs.astype(jnp.float32), sizes)
    with pytest.raises(ValueError, match=r"\(M, K\) and \(G, K, N\)"):
        grouped_matmul(lhs, rhs[0], sizes)
    with pytest.raises(ValueError, match="not made for 64 rows"):
        grouped_matmul(lhs, rhs, group_visits(sizes, 256))
