"""Grouped matmul as a Pallas kernel (``moe_gmm``): ``lhs`` ``(M, K)``, its rows
sorted by group, times ``rhs`` ``(G, K, N)``, each row by its own group's matrix.

It is what an expert layer without drops needs three times a layer
(:func:`accelerate_tpu.ops.moe.dropless_moe`). The schedule is that of jax's
``megablox.gmm`` (``jax/experimental/pallas/ops/tpu/megablox/gmm.py``,
Apache-2.0): the rows are cut into tiles of ``tm``; a group *visits* every row
tile it has a row in, one grid step a visit, multiplies the whole tile by its
matrix and stores only its own rows; groups without rows are never visited, so
``rhs`` may be a stack of which a few groups are live (a layer's experts inside
every layer's, handed over whole: a slice of it would be a copy). What a visit
works on (its group, its row tile, its rows) is computed once from the group
sizes (:func:`group_visits`) and reaches the kernel by scalar prefetch; calls
that share sizes and rows share it.

What differs from the library's kernel: it has a name the chip's trace shows; the
visits are made once and not once a call; every row of the result is written
(rows of no group are zero, where the library leaves what was in memory); the
tiles follow from the operands' shapes; the derivative is
``jax.lax.ragged_dot``'s.
"""

from __future__ import annotations

import functools

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_decode import _resolve_interpret

__all__ = ["GroupVisits", "group_visits", "grouped_matmul"]

# what a tile of ``rhs`` and its second buffer may take of a kernel's fast
# memory (128 MiB on a v5e, of which a kernel gets 16 unasked: ``_call`` asks)
_RHS_TILES_BYTES = 16 * 1024 * 1024
# an ``rhs`` tile narrower than this is fetched in too many, too small copies
_NARROWEST = 512


class GroupVisits(NamedTuple):
    """The grid steps of one grouped matmul over ``rows`` rows: ``count`` visits,
    visit ``v`` multiplies row tile ``tile[v]`` by ``rhs[group[v]]`` and stores
    rows ``lo[v] <= row < hi[v]``. ``sizes`` and ``first_group`` are what it was
    made from (the derivative needs them)."""

    sizes: jax.Array  # (g,) int32
    first_group: jax.Array  # () int32
    group: jax.Array  # (V,) int32
    tile: jax.Array  # (V,) int32
    lo: jax.Array  # (V,) int32
    hi: jax.Array  # (V,) int32
    count: jax.Array  # () int32


def _row_tile(rows: int) -> int:
    """Rows a visit multiplies: 128, or the rows whole (in whole sublane tiles
    of bf16) where they are fewer. A visit's time is the loading of its group's
    weights into the matrix unit, which 16 rows cost as much as 128; past 128
    every row more is masked work for the groups that share the tile (PERF.md,
    PR 33: 16 to 128 read alike at 128 rows, 128 and 256 alike at 2,048, 512
    1.7 times slower)."""
    return min(-(-rows // 16) * 16, 128)


def _tiles(rows: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` from the operands' shapes. ``tn`` is the widest multiple
    of 128 that divides ``N`` (``N`` whole where none does) whose ``(tk, tn)``
    tile of ``rhs`` fits twice; ``K`` is whole where that leaves ``tn`` at least
    ``_NARROWEST``, else cut to its largest divisor that does."""
    budget = _RHS_TILES_BYTES // (2 * itemsize)  # elements of one rhs tile
    widths = [w for w in range(128, n + 1, 128) if n % w == 0] or [n]
    depths = [k] + [d for d in range(k - k % 128, 0, -128) if k % d == 0 and d < k]
    wide = min(w for w in widths if w >= min(_NARROWEST, n))
    for tk in depths:
        fit = [w for w in widths if tk * w <= budget]
        if fit and max(fit) >= wide:
            return _row_tile(rows), tk, max(fit)
    return _row_tile(rows), depths[-1], widths[0]


def group_visits(group_sizes: jax.Array, rows: int, first_group=0) -> GroupVisits:
    """The visits of ``grouped_matmul(lhs, rhs, ...)`` for an ``lhs`` of ``rows``
    rows whose first ``group_sizes[0]`` rows are group ``first_group``'s of
    ``rhs``, the next ``group_sizes[1]`` group ``first_group + 1``'s, and so on;
    every other group of ``rhs`` has no row, and rows past the sizes' sum are
    no group's. ``first_group`` may be traced (a layer's experts inside a
    stack of every layer's)."""
    return _visits(group_sizes, rows, _row_tile(rows), first_group)


def _visits(group_sizes, rows: int, tm: int, first_group) -> GroupVisits:
    group_sizes = group_sizes.astype(jnp.int32)
    g = group_sizes.shape[0]
    tiles_m = -(-rows // tm)
    # Everything below is a comparison against an iota and a sum over it: a
    # handful of small fusions a layer on the chip, no scan, gather or sort.
    # One owner more than groups: the rows no group holds, up to the padded
    # end. Its visits multiply nothing; they are there to zero their tiles
    owners = jnp.arange(g + 1, dtype=jnp.int32)
    upto = owners[None, :g] <= owners[:, None]  # (g + 1, g): groups up to and with owner i
    ends = jnp.sum(jnp.where(upto, group_sizes[None, :], 0), axis=1)
    ends = jnp.where(owners < g, ends, tiles_m * tm)
    starts = jnp.where(owners < g, ends - jnp.pad(group_sizes, (0, 1)), ends[g - 1])
    first_tile = starts // tm
    n_tiles = jnp.where(ends > starts, (ends + tm - 1) // tm - first_tile, 0)
    before = owners[None, :] < owners[:, None]
    visit_start = jnp.sum(jnp.where(before, n_tiles[None, :], 0), axis=1)
    visit_end = visit_start + n_tiles
    # a row tile is visited by each owner with a row in it: at most one visit a
    # tile and one more for each owner that starts inside a tile
    v = jnp.arange(tiles_m + min(g, rows), dtype=jnp.int32)[:, None]
    mine = jnp.logical_and(visit_start[None, :] <= v, v < visit_end[None, :])  # (V, g + 1)
    live = jnp.logical_and(mine, owners[None, :] < g)

    def of_owner(values, which):
        return jnp.sum(jnp.where(which, values[None, :], 0), axis=1)

    # the rows of no group come last: their visits name the matrix already there
    last = jnp.max(jnp.where(group_sizes > 0, owners[:g], 0))
    return GroupVisits(
        sizes=group_sizes,
        first_group=jnp.asarray(first_group, jnp.int32),
        group=of_owner(jnp.where(owners < g, owners, last), mine) + first_group,
        tile=jnp.minimum(of_owner(first_tile - visit_start, mine) + v[:, 0], tiles_m - 1),
        lo=of_owner(starts, live),
        hi=of_owner(ends, live),
        count=visit_end[g],
    )


def _kernel(group, tile, lo, hi, lhs_ref, rhs_ref, out_ref, *scratch, tm, k_tiles):
    del group
    v = pl.program_id(1)
    first, last = lo[v], hi[v]
    new_tile = jnp.logical_or(v == 0, tile[v] != tile[jnp.maximum(v - 1, 0)])
    rows = tile[v] * tm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = jnp.logical_and(rows >= first, rows < last)

    def product():
        return jnp.dot(lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)

    if k_tiles == 1:
        @pl.when(new_tile)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(last > first)
        def _():
            out_ref[...] = jnp.where(mine, product(), out_ref[...])

        return

    (acc_ref,) = scratch
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(last > first)
    def _():
        acc_ref[...] += product()

    @pl.when(k_i == k_tiles - 1)
    def _():
        before = jnp.where(new_tile, 0.0, out_ref[...])
        out_ref[...] = jnp.where(mine, acc_ref[...], before)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _call(lhs, rhs, visits: GroupVisits, tiles, interpret: bool):
    """The kernel on ``lhs`` whose rows are whole row tiles."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles
    k_tiles = k // tk
    n_tiles = pl.cdiv(n, tn)
    live_groups = min(visits.sizes.shape[0], m)
    # both buffers of every operand's tile, and the accumulator
    fast = 2 * (tm * tk + tk * tn) * lhs.dtype.itemsize + (2 + (k_tiles > 1)) * tm * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_tiles=k_tiles),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, visits.count, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, group, tile, lo, hi: (tile[v], k_i)),
                pl.BlockSpec(
                    (None, tk, tn), lambda n_i, v, k_i, group, tile, lo, hi: (group[v], k_i, n_i)
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, group, tile, lo, hi: (tile[v], n_i)
            ),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] if k_tiles > 1 else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(fast + fast // 4, 16 * 1024 * 1024),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            transcendentals=0,
            bytes_accessed=(
                lhs.size * lhs.dtype.itemsize * n_tiles
                + live_groups * k * n * rhs.dtype.itemsize
                + m * n * 4
            ),
        ),
        interpret=interpret,
        name="moe_gmm",
    )(visits.group, visits.tile, visits.lo, visits.hi, lhs, rhs)


def _ragged(lhs, rhs, visits: GroupVisits):
    """The same product as ``jax.lax.ragged_dot`` computes it, which wants the
    sizes of every group of ``rhs``: the derivative, and the tests' oracle."""
    sizes = visits.sizes
    if sizes.shape[0] != rhs.shape[0]:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), sizes, (visits.first_group,)
        )
    return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, visits: GroupVisits, interpret: bool):
    m, k = lhs.shape
    tiles = _tiles(m, k, rhs.shape[2], rhs.dtype.itemsize)
    pad = -m % tiles[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return _call(lhs, rhs, visits, tiles, interpret)[:m]


def _gmm_fwd(lhs, rhs, visits, interpret):
    return _gmm(lhs, rhs, visits, interpret), (lhs, rhs, visits)


def _gmm_bwd(interpret, saved, cotangent):
    del interpret
    lhs, rhs, visits = saved
    _, pull = jax.vjp(lambda a, b: _ragged(a, b, visits), lhs, rhs)
    return (*pull(cotangent), None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    groups: Union[jax.Array, GroupVisits],
    *,
    interpret=None,
) -> jax.Array:
    """``lhs`` ``(M, K)``, rows sorted by group, times ``rhs`` ``(G, K, N)``:
    row ``r`` of the float32 result ``(M, N)`` is ``lhs[r] @ rhs[group of r]``,
    summed in float32; a row of no group is zero. ``groups`` is the ``(G,)``
    int32 rows of every group, or :func:`group_visits` of them, made once for
    calls that share sizes and row count (then ``rhs`` may hold more groups
    than the sizes name, see there). Both operands in one dtype.

    On a TPU this is one ``moe_gmm`` kernel whose tiles follow from ``M``, ``K``
    and ``N``; elsewhere the same kernel interpreted (``interpret=None``
    resolves by platform). Differentiable in ``lhs`` and ``rhs``: the backward
    pass is ``jax.lax.ragged_dot``'s."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(f"grouped_matmul wants (M, K) and (G, K, N), got {lhs.shape} and {rhs.shape}")
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul wants one dtype, got {lhs.dtype} and {rhs.dtype}")
    visits = groups if isinstance(groups, GroupVisits) else group_visits(groups, lhs.shape[0])
    rows = lhs.shape[0]
    if visits.group.shape[0] != -(-rows // _row_tile(rows)) + min(visits.sizes.shape[0], rows):
        raise ValueError(f"these visits were not made for {rows} rows")
    return _gmm(lhs, rhs, visits, _resolve_interpret(interpret))
