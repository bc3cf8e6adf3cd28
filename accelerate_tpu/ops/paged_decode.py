"""Pallas TPU flash-decode kernels over the paged KV pool + fused sampling.

The paged decode path's reference semantics (`ops/attention.py::
paged_attention` / `verify_attention`) first gather ``pool[tables]`` into a
dense per-slot context — HBM traffic scales with *arena capacity* (every
table entry, live or null, is materialized and, for int8 pools, dequantized
in full) rather than with live tokens. The kernels here walk each slot's
block table *inside* the kernel instead:

* **`paged_flash_decode`** — one query token per slot. Grid ``(slots,)``;
  the pools come in whole and stay in HBM (``memory_space=pl.ANY``), the
  block tables and per-slot positions ride in as scalar-prefetch operands,
  and the kernel walks a slot's own ``ceil((pos + 1) / T)`` chunks of ``T``
  positions (``decode_chunk_positions``: the blocks that fill a 128-lane
  score tile, four such tiles over a latent pool) in a loop. A chunk's
  live blocks are copied by ``pltpu.make_async_copy`` — pool block
  ``tables[slot, j]`` whole (all KV heads: one contiguous copy) — into one
  of two buffers, so that the next
  chunk, or the next slot's first, is in flight while this one is computed.
  Over a latent pool a chunk whose live blocks are at most two runs of the
  pool (``decode_chunk_runs``, made once a step) is copied a power of two
  blocks at a time, one copy for each set bit of a run's length.
  Blocks wholly past a slot's position are *neither fetched nor weighted*
  — exactly the contract documented on ``paged_attention`` (masked scores
  softmax to an exp-underflow-exact 0.0, so skipping == computing): a dead
  grid step no longer exists, and what a dead block's rows of the buffer
  still hold from an earlier chunk is read as zeros (0 x a stale NaN would
  be a NaN in ``P x V``). For a live slot the skipped tail *is* the row's
  null-block padding (allocation covers every position ``<= pos``), so
  released/unallocated entries are never read as real context. A chunk's
  heads are computed together with the positions on the lane axis: the
  query is ``(rows, h_kv * d)``, one row a query head and zero outside the
  lanes of its own KV head, so ``S = Q . K^T`` is one matmul into a
  ``(rows, T)`` f32 tile, the online softmax (running max and sum per row)
  works on whole tiles, and ``P x V`` is one matmul into a ``(rows, h_kv *
  d)`` f32 accumulator of which row ``r`` is read on its own head's lanes
  only. That spends ``h_kv`` times the FLOPs on an MXU that was idle and is
  the same algorithm for every ``n_rep`` and ``head_dim``: KV is read once
  per *group*, never repeated ``n_rep``×. int8 pools stay int8 in HBM and in
  the buffers; their per-(block, position) scales go on the score and weight
  columns (int8 values are exact in bf16) — only live columns' scales are
  ever applied.

* **`paged_flash_verify`** — the W-token speculative-verify window. It
  keeps the walk the decode kernel had before: grid ``(slots,
  blocks_per_row + 1)``, the table walk in the kv tiles' BlockSpec index
  map (one block a grid step, a dead block's step skipped by ``@pl.when``
  but still paid), a loop over the KV heads of the fetched block with the
  grouped-GQA layout (q is ``(h_kv, n_rep * W, d)``), online softmax in
  acc/m/l VMEM scratch, int8 dequantized per fetched tile. Committed
  history is masked *strictly* ``k_pos < pos``
  (the window's own columns are NOT in the pool — the engine commits only
  the accepted prefix afterwards); one extra grid step attends the window
  K/V operands causally (``k_idx <= q_idx``), reproducing
  ``verify_attention``'s ``k_pos <= pos + q_idx`` mask without ever
  scatter-writing a temporary view.

* **`fused_sample`** — the sampling epilogue, semantics pinned by
  ``engine.py::_filter_logits`` / ``_sample_rows``: temperature scaling,
  top-k, top-p ("nucleus") filtering and the categorical draw fused into
  one kernel, eight slot rows per program instance. Instead of materializing
  a sorted copy of the logits (the reference's ``sort``/``cumsum``), both
  filters reduce to *threshold* comparisons computed by a 32-step binary
  search over the order-preserving uint32 image of f32 — the k-th largest
  value exactly, and the top-p cutoff via the value-level characterization
  ``keep x  iff  sum(exp(y - m) for kept y > x) < p * Z`` (provably equal
  to the reference's sorted-cutoff rule, ties included; see the comment on
  ``_sample_kernel``). The categorical draw takes pre-generated Gumbel
  noise as an operand — ``argmax(filtered + gumbel(key))`` is bitwise what
  ``jax.random.categorical`` computes, and TPU in-kernel PRNG
  (``pltpu.prng_seed``) has no CPU interpret lowering, which would break
  the tier-1 validation story.

All three follow ``flash_attention.py``'s platform idiom: ``interpret=None``
resolves to ``jax.default_backend() != "tpu"``, so the same call sites run
the Mosaic kernel on TPU and the interpret-mode evaluator (bit-identical
semantics, CPU) everywhere else — the basis of
``runs/kernel_validation_cpu_interpret.jsonl``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

__all__ = [
    "paged_flash_decode", "paged_flash_verify", "fused_sample",
    "decode_chunk_positions", "decode_walked_positions", "decode_chunk_runs",
    "decode_copy_plan", "decode_copy_descriptors",
]


def _dot_f32(a, b, transpose_b=False):
    """MXU matmul with an f32 accumulator (G402), operands in storage dtype."""
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# ------------------------------------------------------------ decode kernel
def _online_softmax_update(g, s, v, acc_ref, m_ref, l_ref):
    """Fold one tile's masked scores ``s`` (rows, n) and values ``v`` (n, d)
    into kv head ``g``'s running (acc, m, l)."""
    m_prev = m_ref[g]  # (rows, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    pexp = jnp.exp(s - m_cur)
    l_ref[g] = alpha * l_ref[g] + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[g] = acc_ref[g] * alpha + _dot_f32(pexp.astype(v.dtype), v)
    m_ref[g] = m_cur


def _load_kv_head(k_ref, v_ref, g, d, scales):
    """KV head ``g`` of the fetched pool block as two (bs, d) tiles. The
    block holds every KV head side by side on the lane axis — ``(1, bs,
    h_kv * d)``, the pool's own row-major tile (kvcache.py, "The pool's
    layout") — so one head is a static slice of lanes."""
    k = k_ref[0, :, g * d:(g + 1) * d]
    v = v_ref[0, :, g * d:(g + 1) * d]
    if scales is not None:
        ks_ref, vs_ref = scales  # (1, 1, bs, 1): this block's scale column
        k = k.astype(jnp.float32) * ks_ref[0, 0]
        v = v.astype(jnp.float32) * vs_ref[0, 0]
    return k, v


# One chunk of the decode kernel's walk is this many positions: the lane
# width of a score tile. A chunk is `decode_chunk_positions(block_size)`
# positions, a whole number of blocks; the engine's `kv_walked_tokens` counter
# is reckoned from the same two functions, so the two cannot drift.
_CHUNK_LANES = 128
# A latent chunk carries one row a position where a per-head chunk carries
# keys and values of every kv head, so 128 of its positions are too few bytes
# for a chunk's fixed work (the descriptors' loop, the dependent chain of two
# matmuls and a softmax update): at 512 it carries a GPT-2 chunk's 655 KB.
_LATENT_CHUNK_LANES = 512


def decode_chunk_positions(block_size: int, latent: bool = False) -> int:
    """Positions the decode kernel folds into its softmax at a time: the
    blocks that fill a 128-lane score tile, or four such tiles over a latent
    pool (one block where a block is wider)."""
    lanes = _LATENT_CHUNK_LANES if latent else _CHUNK_LANES
    return max(lanes // block_size, 1) * block_size


def decode_walked_positions(live: int, block_size: int, latent: bool = False) -> int:
    """Positions `paged_flash_decode` computes on for a slot that holds
    ``live`` positions (its ``pos + 1``): whole chunks, and one chunk for a
    slot that holds nothing (a vacant slot rides at position 0)."""
    chunk = decode_chunk_positions(block_size, latent)
    return max(-(-live // chunk), 1) * chunk


def decode_chunk_runs(block_tables, block_size: int):
    """How each chunk of a latent pool's rows breaks into runs of the pool,
    for `paged_flash_decode`'s copies: ``(B, chunks)`` int32, ``head + (reach
    << 16)``, where ``head`` of a chunk's leading blocks are one run (each
    block the one before it plus one) and ``reach`` of them are at most two.
    numpy in, numpy out (the engine's count at admission); a traced array in,
    one small XLA computation (the engine's decode step makes it once, outside
    its loop over layers). A layer's offset on the tables keeps every run."""
    xp = np if isinstance(block_tables, np.ndarray) else jnp
    C = decode_chunk_positions(block_size, latent=True) // block_size
    b, bpr = block_tables.shape
    chunks = xp.pad(block_tables, ((0, 0), (0, -bpr % C))).reshape(b, -1, C)
    breaks = xp.cumsum(chunks[..., 1:] != chunks[..., :-1] + 1, axis=-1)
    head = 1 + xp.sum(breaks == 0, axis=-1)
    reach = 1 + xp.sum(breaks <= 1, axis=-1)
    return (head + reach * (1 << 16)).astype(xp.int32)


def _chunk_split(runs, live):
    """Where a chunk's ``live`` blocks split into their two runs (``live``
    itself where they are one), or -1 where they are more than two."""
    head, reach = runs & 0xFFFF, runs >> 16
    if isinstance(live, np.ndarray):
        return np.where(live <= reach, np.minimum(head, live), -1)
    return jnp.where(live <= reach, jnp.minimum(head, live), -1)


def decode_copy_plan(row, block_size: int, latent: bool = False) -> np.ndarray:
    """Copies `paged_flash_decode` issues over one leaf of one layer for a slot
    whose table row is ``row``, by how many of its blocks are live: entry
    ``n`` for the row's first ``n`` blocks (entry 0, a slot that holds
    nothing, rides at position 0 on one live block). A per-head pool's blocks
    take one copy each. Over a latent pool, a chunk's live blocks that are at
    most two runs in the pool take one copy for each set bit of each run's
    length, other live blocks one copy each. The kernel follows the same rule
    (``_decode_kernel``)."""
    row = np.asarray(row, np.int64)
    n = np.arange(1, row.size + 1)
    if not latent:
        return np.concatenate([[1], n])
    C = decode_chunk_positions(block_size, latent) // block_size
    live = np.arange(1, C + 1)
    split = _chunk_split(decode_chunk_runs(row[None], block_size)[0][:, None], live)
    bits = np.bitwise_count
    cost = np.where(split >= 0, bits(np.maximum(split, 0)) + bits(live - split), live)
    before = np.concatenate([[0], np.cumsum(cost[:, -1])])  # the whole chunks ahead
    plan = before[(n - 1) // C] + cost[(n - 1) // C, (n - 1) % C]
    return np.concatenate([plan[:1], plan])


def decode_copy_descriptors(row, live: int, block_size: int, latent: bool = False) -> int:
    """Copies `paged_flash_decode` issues over one leaf of one layer for a slot
    that holds ``live`` positions (its ``pos + 1``) through table row ``row``
    (:func:`decode_copy_plan`)."""
    blocks = min(-(-live // block_size), len(row))
    return int(decode_copy_plan(row, block_size, latent)[blocks])


def _pieces(blocks: int):
    """Static sizes of the copies that move a run of up to ``blocks`` blocks:
    one power of two for each bit a count of blocks can have, the largest
    first. A run of ``n`` blocks takes the sizes of ``n``'s set bits, each at
    the offset of the larger ones before it."""
    return [1 << k for k in reversed(range(blocks.bit_length()))]


def _decode_kernel(tables_ref, pos_ref, *refs, block_size, chunk_blocks, blocks_per_row,
                   n_rep, d, scale, softcap, quantized, value_dim):
    # a latent pool (``value_dim``) has no pool of values and no buffer for
    # them: a row's values are its own first ``value_dim`` columns; its
    # chunks' runs (``decode_chunk_runs``) ride in as a third scalar operand
    latent = value_dim is not None
    runs_ref, q_ref, k_hbm, *rest = refs if latent else (None, *refs)
    if latent:
        v_hbm = vbuf = None
        o_ref, kbuf, sem, acc_ref, base_ref = rest
    elif quantized:
        v_hbm, ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, acc_ref, base_ref = rest
    else:
        v_hbm, o_ref, kbuf, vbuf, sem, acc_ref, base_ref = rest
    bs, C = block_size, chunk_blocks
    T = C * bs
    rows, lanes = acc_ref.shape
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    p = pos_ref[b]
    # chunks that hold a position <= pos; never past the row's table, and at
    # least one: a slot that walked none (pos < 0) would start no copy for
    # the slot after it, which then waits for ever
    n = jnp.clip(p // T, 0, (blocks_per_row - 1) // C) + 1

    def each_live_block(slot_b, chunk, buf, fn):
        """``fn(copy_k, copy_v, jj, live)`` for each block of ``chunk`` of
        slot ``slot_b``'s row; a block is live when its first position is
        <= the slot's position (and it is in the table at all)."""
        pp = pos_ref[slot_b]

        def block(jj, carry):
            j = chunk * C + jj
            live = jnp.logical_and(j * bs <= pp, j < blocks_per_row)
            blk = tables_ref[slot_b, jnp.minimum(j, blocks_per_row - 1)]
            fn(
                pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, jj], sem.at[0, buf]),
                None if latent else
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf, jj], sem.at[1, buf]),
                jj, live,
            )
            return carry

        # unrolled: rolled, the descriptors' scalar work reads 7-12% slower a call
        lax.fori_loop(0, C, block, 0, unroll=True)

    def start_blocks(slot_b, chunk, buf):
        def fn(copy_k, copy_v, jj, live):
            @pl.when(live)
            def _():
                copy_k.start()
                if copy_v is not None:
                    copy_v.start()
        each_live_block(slot_b, chunk, buf, fn)

    def zero(buf, jj):
        # A block past the slot's position is never fetched: its rows of the
        # buffer hold whatever an earlier chunk left there. Its scores are
        # masked below, but a weight of exactly 0 times a stale NaN is a NaN
        # in P x V: its value rows read as zeros.
        held = kbuf if latent else vbuf  # a latent row is its own value
        held[buf, jj] = jnp.zeros(held.shape[2:], held.dtype)

    def wait_blocks(slot_b, chunk, buf):
        def fn(copy_k, copy_v, jj, live):
            @pl.when(live)
            def _():
                copy_k.wait()  # graft: wait-ok — a DMA semaphore in the kernel, not a thread
                if copy_v is not None:
                    copy_v.wait()  # graft: wait-ok

            pl.when(jnp.logical_not(live))(lambda: zero(buf, jj))
        each_live_block(slot_b, chunk, buf, fn)

    start, wait = start_blocks, wait_blocks
    if latent:
        # A latent chunk's live blocks that are at most two runs in the pool
        # (``decode_chunk_runs``: the first ``split`` of them and the rest)
        # are copied a power of two blocks of a run at a time, one copy for
        # each set bit of the run's length (``_pieces``), where 32 copies a
        # chunk, one a block, cost more than the chunk's bytes; other chunks
        # a block at a time. The rule is ``decode_copy_plan``'s.
        def live_and_split(slot_b, chunk):
            held = jnp.minimum(pos_ref[slot_b] // bs + 1, blocks_per_row)
            live = jnp.clip(held - chunk * C, 0, C)  # the chunk's leading blocks
            return live, _chunk_split(runs_ref[slot_b, chunk], live)

        def block_of(slot_b, chunk, jj):
            j = chunk * C + jj
            return tables_ref[slot_b, jnp.minimum(j, blocks_per_row - 1) if blocks_per_row % C else j]

        def each_piece(slot_b, chunk, buf, live, split, fn):
            runs = [(block_of(slot_b, chunk, 0), 0, split),
                    (block_of(slot_b, chunk, jnp.minimum(split, C - 1)), split, live - split)]
            # a run is no longer than the chunk, the row or the pool
            for size in _pieces(min(C, blocks_per_row, k_hbm.shape[0])):
                for first, at, length in runs:
                    off = length // (2 * size) * (2 * size)  # the larger pieces before it
                    copy = pltpu.make_async_copy(k_hbm.at[pl.ds(first + off, size)],
                                                 kbuf.at[buf, pl.ds(at + off, size)],
                                                 sem.at[0, buf])
                    pl.when((length & size) != 0)(functools.partial(fn, copy))

        def start(slot_b, chunk, buf):
            live, split = live_and_split(slot_b, chunk)
            pl.when(split >= 0)(lambda: each_piece(slot_b, chunk, buf, live, split,
                                                   lambda copy: copy.start()))
            pl.when(split < 0)(lambda: start_blocks(slot_b, chunk, buf))

        def wait(slot_b, chunk, buf):
            live, split = live_and_split(slot_b, chunk)

            @pl.when(split >= 0)
            def _():
                each_piece(slot_b, chunk, buf, live, split,
                           lambda copy: copy.wait())  # graft: wait-ok

                @pl.when(live < C)  # only a slot's last chunk has blocks past it
                def _():
                    for jj in range(C):
                        pl.when(jj >= live)(functools.partial(zero, buf, jj))

            pl.when(split < 0)(lambda: wait_blocks(slot_b, chunk, buf))

    @pl.when(b == 0)
    def _first():
        base_ref[0] = 0
        start(0, 0, 0)

    base = base_ref[0]  # chunks walked by the slots before this one
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk_tiles(buf):
        """The chunk's keys and values as two (T, lanes) tiles in the
        operands' dtype (an int8 pool's blocks are widened as they are read;
        their scales go on the score and weight columns)."""
        def tile(ref):
            blocks = [ref[buf, jj] for jj in range(C)]
            if quantized:
                blocks = [x.astype(jnp.float32).astype(q_ref.dtype) for x in blocks]
            return blocks[0] if C == 1 else jnp.concatenate(blocks, axis=0)
        k = tile(kbuf)
        # read once: the values of a latent chunk are columns of the tile at hand
        return k, (k[:, :value_dim] if latent else tile(vbuf))

    def body(i, carry):
        m_prev, l_prev = carry
        # the next chunk, or the next slot's first, is in flight into the
        # other buffer while this one is computed
        buf = (base + i) % 2

        @pl.when(i + 1 < n)
        def _next_chunk():
            start(b, i + 1, 1 - buf)

        @pl.when(jnp.logical_and(i + 1 == n, b + 1 < nb))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        wait(b, i, buf)
        k, v = chunk_tiles(buf)
        # row r is query head r against its own kv head's keys: q's row is
        # zero outside that head's lanes
        s = _dot_f32(q_ref[0], k, transpose_b=True) * scale  # (rows, T), f32
        if quantized:
            s = s * ks_ref[0, i]
        if softcap is not None:  # Gemma-2 tanh capping, pre-mask
            s = softcap * jnp.tanh(s / softcap)
        live = i * T + lax.broadcasted_iota(jnp.int32, s.shape, 1) <= p
        s = jnp.where(live, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur)
        l_cur = alpha * l_prev + jnp.sum(pexp, axis=-1, keepdims=True)
        if quantized:  # select, not multiply: a dead block's scale is anything
            pexp = jnp.where(live, pexp * vs_ref[0, i], 0.0)
        acc_ref[...] = acc_ref[...] * alpha + _dot_f32(pexp.astype(v.dtype), v)
        return m_cur, l_cur

    # chunk 0 holds position 0 <= pos, so l > 0 at the end
    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    _, l = lax.fori_loop(0, n, body, (m0, jnp.zeros((rows, 1), jnp.float32)))
    base_ref[0] = base + n

    if latent:  # one shared head: every row's lanes are its own result
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return

    # Row r of the accumulator is right on its own kv head's lanes only.
    # Heads narrower than a 128-lane tile are picked a whole tile at a time
    # (a select on aligned lanes), then each row takes its own head's part of
    # that tile; slicing every head's lanes out on its own costs a lane
    # rotation a head (1.4 us a slot at 20 heads of 64; my chip run, PR 31).
    width = 128 if 128 % d == 0 and lanes % 128 == 0 else d
    per = width // d  # heads a tile
    row = lax.broadcasted_iota(jnp.int32, (rows, width), 0)

    def rows_of(first_head, heads):
        return jnp.logical_and(row >= first_head * n_rep, row < (first_head + heads) * n_rep)

    tile = jnp.zeros((rows, width), jnp.float32)
    later = [jnp.zeros((rows, width), jnp.bool_)] * (per - 1)  # rows of a tile's 2nd, 3rd.. head
    for t in range(lanes // width):
        tile = jnp.where(rows_of(t * per, per), acc_ref[:, t * width:(t + 1) * width], tile)
        later = [jnp.logical_or(m, rows_of(t * per + s, 1)) for s, m in enumerate(later, 1)]
    out = tile[:, :d]
    for s, m in enumerate(later, 1):
        out = jnp.where(m[:, :d], tile[:, s * d:(s + 1) * d], out)
    o_ref[0] = (out / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flat_pools(pools, scales, block_tables, layer, h, d):
    """The kernels' view of the pools: each ``(blocks, bs, h_kv * d)``, the
    scales ``(blocks, bs)``, and block tables that index them. Returns
    ``(pools, scales, tables, h_kv, bs)``.

    One layer's pool ``(num_blocks, bs, ...)`` is taken as it is. With
    ``layer`` (a traced scalar) a pool is every layer's, ``(L, num_blocks,
    bs, ...)``: it is flattened over its two leading axes — no bytes move in
    the row-major layout it lives in — and the layer is reached through the
    tables, ``tables + layer * num_blocks``, so a layer loop that carries
    the pool whole never slices it. A pool's head axes may come apart
    ``(h_kv, d)`` or merged into one; the scales follow the pool's leading
    axes."""
    lead = 1 if layer is None else 2
    shape = pools[0].shape
    bs, lanes = shape[lead], math.prod(shape[lead + 1:])
    if lanes % d != 0 or h % (lanes // d) != 0:
        raise ValueError(
            f"a pool of shape {shape} does not hold kv heads of {d} that "
            f"divide {h} query heads"
        )
    tables = block_tables.astype(jnp.int32)
    if layer is not None:
        tables = tables + jnp.asarray(layer, jnp.int32) * shape[1]
    pools = [pool.reshape(-1, bs, lanes) for pool in pools]
    scales = [None if s is None else s.reshape(-1, bs) for s in scales]
    return pools, scales, tables, lanes // d, bs


def _gathered_scale_columns(scale, block_tables):
    """Per-slot scale columns for the kernels: ``scale`` (blocks, bs) →
    (B, blocks_per_row, bs, 1), gathered through the tables by XLA. A
    ``(1, bs)`` row of the pool-shaped array is not a tile the TPU lowering
    accepts; this copy is 4 bytes a position beside the ``2 * h_kv * d`` the
    kernel reads for it, and only live blocks' columns are ever applied."""
    return scale[block_tables][..., None]


def _gathered_scale_rows(scale, block_tables, n_chunks, chunk):
    """Per-slot scale rows for the decode kernel: ``scale`` (blocks, bs) →
    (B, n_chunks, 1, chunk), a chunk's positions along the lanes as its
    scores have them, gathered through the tables by XLA (4 bytes a position
    beside the ``2 * h_kv * d`` the kernel reads for it; only live columns
    are ever applied)."""
    b = block_tables.shape[0]
    flat = scale[block_tables].reshape(b, -1)
    flat = jnp.pad(flat, ((0, 0), (0, n_chunks * chunk - flat.shape[1])))
    return flat.reshape(b, n_chunks, 1, chunk)


def paged_flash_decode(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: Optional[jax.Array],
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    value_dim: Optional[int] = None,
    chunk_runs: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-token paged decode attention as a Pallas flash kernel.

    Shapes match :func:`~accelerate_tpu.ops.attention.paged_attention`
    (the reference this kernel is parity-gated against): ``q`` (B, 1, h, d),
    ``k_pool``/``v_pool`` (num_blocks, block_size, h_kv, d) — int8 with
    ``k_scale``/``v_scale`` (num_blocks, block_size) — ``block_tables``
    (B, blocks_per_row) int32, ``pos`` (B,) int32. Returns (B, 1, h, d).
    With ``layer``, a traced scalar, the pools (and scales) are every
    layer's, stacked on a leading axis, and the kernel reads that layer's
    blocks of them in place (:func:`_flat_pools`); the pool's two head axes
    may be merged into one of ``h_kv * d``, the form the engine stores.

    HBM bytes per step are ``live_blocks * block_size * h_kv * d *
    itemsize * 2`` (+ scales) instead of the reference gather's
    ``B * blocks_per_row * block_size * ...`` materialization: the kernel
    copies a slot's live blocks itself, a chunk of
    :func:`decode_chunk_positions` positions at a time, dead tail blocks are
    neither fetched nor computed on, and int8 stays int8 in HBM (widened per
    chunk in VMEM). Precision: operands in the pool's dtype, scores, running
    max, sum and accumulator in float32, ``P`` rounded to the values' dtype
    before the second matmul. ``scale`` defaults to ``1/sqrt(d)``;
    the model path passes its ``query_pre_attn_scalar`` override.
    ``softcap`` is the static Gemma-2 tanh cap. Sliding-window masking is
    NOT supported — callers with a sliding-window config must use the
    reference op (the engine enforces this fallback).

    A latent pool: ``value_dim`` with ``v_pool=None``. The pool holds one row
    of ``d`` values a position under every query head (one shared "kv head"),
    a row's first ``value_dim`` columns are also its value, and each live row
    is copied out of HBM once and serves both matmuls. Returns (B, 1, h,
    ``value_dim``).

    The kernel copies a chunk's live blocks one at a time, but over a latent
    pool a chunk whose live blocks are at most two runs in the pool a power of
    two blocks of a run at a time, one copy for each set bit of each run's
    length (:func:`decode_copy_plan`). ``chunk_runs``, a latent pool's alone,
    is :func:`decode_chunk_runs` of the tables, made here where it is not
    handed in (a caller that calls this once a layer makes it once).
    """
    walk = _latent_decode if value_dim is not None else _paged_flash_decode
    return walk(q, k_pool, v_pool, block_tables, pos, k_scale=k_scale, v_scale=v_scale,
                scale=scale, softcap=softcap, layer=layer, value_dim=value_dim,
                chunk_runs=chunk_runs, interpret=interpret)


def _paged_flash_decode(q, k_pool, v_pool, block_tables, pos, *, k_scale, v_scale, scale,
                        softcap, layer, value_dim, chunk_runs, interpret):
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged_flash_decode takes one query token, got {sq}")
    latent = value_dim is not None
    if latent != (v_pool is None) or (latent and k_scale is not None):
        raise ValueError(
            "a latent pool (value_dim) is one unquantized pool whose rows hold their "
            "own values: pass v_pool=None and no scales with it, and only with it"
        )
    pools = (k_pool,) if latent else (k_pool, v_pool)
    pools, (k_scale, v_scale), block_tables, h_kv, bs = _flat_pools(
        pools, (k_scale, v_scale), block_tables, layer, h, d
    )
    if latent and (h_kv != 1 or not 0 < value_dim <= d):
        raise ValueError(
            f"a latent pool has one row of {d} a position with its value in the "
            f"first {value_dim} columns, got rows of {h_kv * d}"
        )
    k_pool = pools[0]
    n_rep = h // h_kv
    bpr = block_tables.shape[1]
    lanes = h_kv * d
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = _resolve_interpret(interpret)
    quantized = k_scale is not None
    chunk = decode_chunk_positions(bs, latent)
    chunk_blocks = chunk // bs
    n_chunks = -(-bpr // chunk_blocks)
    # the scalar operands: the tables, the positions and, over a latent pool,
    # how its chunks break into runs
    scalars = [block_tables, pos.astype(jnp.int32)]
    if latent:
        if chunk_runs is None:
            chunk_runs = decode_chunk_runs(block_tables, bs)
        if chunk_runs.shape != (b, n_chunks):
            raise ValueError(
                f"chunk_runs {chunk_runs.shape} is not decode_chunk_runs of these tables: "
                f"({b}, {n_chunks}) for chunks of {chunk_blocks} blocks"
            )
        scalars.append(chunk_runs)
    elif chunk_runs is not None:
        raise ValueError("chunk_runs is a latent pool's: a per-head pool is copied a block at a time")

    # one row a query head, zero outside the lanes of its own kv head, padded
    # to whole sublane tiles of the operand's dtype. The query is laid flat
    # first (row i: each group's i-th head on its group's lanes) and then
    # repeated over the kv heads and selected, so that no head's d lanes have
    # to be moved into a row of h_kv * d by a relayout
    sublanes = 8 * max(4 // q.dtype.itemsize, 1)
    rows = -(-h // sublanes) * sublanes
    qi = q.reshape(b, h_kv, n_rep, d).transpose(0, 2, 1, 3).reshape(b, 1, n_rep, lanes)
    own = jnp.repeat(jnp.eye(h_kv, dtype=bool), d, axis=1)  # (h_kv, lanes)
    qx = jnp.where(own[None, :, None, :], qi, 0).reshape(b, h, lanes)
    qx = jnp.pad(qx, ((0, 0), (0, rows - h), (0, 0)))

    q_spec = pl.BlockSpec((1, rows, lanes), lambda bb, *_: (bb, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec] + [pool_spec] * len(pools)
    args = [qx, *pools]
    d_out = value_dim if latent else d  # a result row's width
    value_buffers = [] if latent else [
        pltpu.VMEM((2, chunk_blocks, bs, lanes), pools[1].dtype)]
    if quantized:
        s_spec = pl.BlockSpec((1, n_chunks, 1, chunk), lambda bb, *_: (bb, 0, 0, 0))
        in_specs += [s_spec, s_spec]
        args += [
            _gathered_scale_rows(k_scale, block_tables, n_chunks, chunk),
            _gathered_scale_rows(v_scale, block_tables, n_chunks, chunk),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, d_out), lambda bb, *_: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_blocks, bs, lanes), k_pool.dtype),
            *value_buffers,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows, d_out if latent else lanes), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_size=bs, chunk_blocks=chunk_blocks,
            blocks_per_row=bpr, n_rep=n_rep, d=d, scale=scale, softcap=softcap,
            quantized=quantized, value_dim=value_dim,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d_out), q.dtype),
        # the walk carries its buffers from one slot to the next
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(*scalars, *args)
    return out[:, :h].reshape(b, 1, h, d_out)


# A latent chunk unrolls 32 blocks' copies where a per-head chunk unrolls 8,
# and lowering that kernel once for every layer of a step took a serving
# cell's set-up 5 s longer on the chip's host (PERF.md, section 6). Jitted, the
# layers' calls, which are the same, trace and lower it once; XLA inlines the
# call, so the step runs the same kernels.
_latent_decode = jax.jit(
    _paged_flash_decode, static_argnames=("scale", "softcap", "value_dim", "interpret"))


# ------------------------------------------------------------ verify kernel
def _verify_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, wk_ref, wv_ref,
                   *rest, block_size, h_kv, d, w, n_rep, scale, softcap, quantized):
    if quantized:
        *scales, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        scales = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)  # blocks_per_row + 1 (last step = the window)
    p = pos_ref[b]
    rows = n_rep * w

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _scores(q, k):
        s = _dot_f32(q, k, transpose_b=True) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        return s

    # History phase: committed pool blocks, masked STRICTLY k_pos < pos —
    # the window's own positions [pos, pos+W) are not in the pool (the
    # engine commits only the accepted prefix afterwards), they arrive as
    # the wk/wv operands below. k_pos < p <= p + q_idx, so the strict
    # history mask is uniform across the window's queries, matching
    # verify_attention's k_pos <= pos + q_idx on every committed position.
    @pl.when((j < nj - 1) & (j * block_size < p))
    def _history():
        for g in range(h_kv):
            k, v = _load_kv_head(k_ref, v_ref, g, d, scales)
            s = _scores(q_ref[0, g], k)  # (rows, bs)
            k_pos = j * block_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < p, s, NEG_INF)
            _online_softmax_update(g, s, v, acc_ref, m_ref, l_ref)

    # Window phase (last grid step): the W fresh K/V columns, attended
    # causally within the window — query q_idx sees window key k_idx iff
    # pos + k_idx <= pos + q_idx. Query 0 always sees key 0, so l > 0 at
    # finalize even when no history block survives (pos == 0).
    @pl.when(j == nj - 1)
    def _window():
        # q row layout: (head-in-group r) * w + (window index q_idx)
        row = lax.broadcasted_iota(jnp.int32, (rows, w), 0)
        q_idx = row
        for r in range(1, n_rep):
            q_idx = q_idx - jnp.where(row >= r * w, w, 0)
        causal = lax.broadcasted_iota(jnp.int32, (rows, w), 1) <= q_idx
        for g in range(h_kv):
            v = wv_ref[0, :, g, :]  # (w, d) — full precision, never quantized
            s = _scores(q_ref[0, g], wk_ref[0, :, g, :])  # (rows, w)
            s = jnp.where(causal, s, NEG_INF)
            _online_softmax_update(g, s, v, acc_ref, m_ref, l_ref)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_verify(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    win_k: jax.Array,
    win_v: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Speculative-verify window attention as a Pallas flash kernel.

    ``q`` (B, W, h, d) at absolute positions ``pos[b] + q_idx``; committed
    history comes from the paged pool (same table walk and int8 dequant as
    :func:`paged_flash_decode`, masked strictly ``k_pos < pos``), while the
    window's own K/V — NOT yet committed — ride in as ``win_k``/``win_v``
    (B, W, h_kv, d) operands attended causally in-register. Together that
    reproduces :func:`~accelerate_tpu.ops.attention.verify_attention`'s
    ``k_pos <= pos + q_idx`` mask without the reference path's
    scatter-write of a temporary dense view. ``layer`` addresses stacked
    pools as in :func:`paged_flash_decode`. Returns (B, W, h, d).
    """
    b, w, h, d = q.shape
    (k_pool, v_pool), (k_scale, v_scale), block_tables, h_kv, bs = _flat_pools(
        (k_pool, v_pool), (k_scale, v_scale), block_tables, layer, h, d
    )
    n_rep = h // h_kv
    bpr = block_tables.shape[1]
    rows = n_rep * w
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = _resolve_interpret(interpret)
    quantized = k_scale is not None

    # (B, W, h, d) -> (B, h_kv, n_rep * W, d), row = r * W + q_idx
    qf = q.reshape(b, w, h_kv, n_rep, d).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b, h_kv, rows, d)

    def _pool_block(bb, j, t):
        # clamped on the (skipped) window step so the map stays total
        return t[bb, jnp.minimum(j, bpr - 1)]

    q_spec = pl.BlockSpec((1, h_kv, rows, d), lambda bb, j, t, p: (bb, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, bs, h_kv * d), lambda bb, j, t, p: (_pool_block(bb, j, t), 0, 0)
    )
    win_spec = pl.BlockSpec((1, w, h_kv, d), lambda bb, j, t, p: (bb, 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec, win_spec, win_spec]
    args = [qf, k_pool, v_pool, win_k, win_v]
    if quantized:
        s_spec = pl.BlockSpec(
            (1, 1, bs, 1), lambda bb, j, t, p: (bb, jnp.minimum(j, bpr - 1), 0, 0)
        )
        in_specs += [s_spec, s_spec]
        args += [
            _gathered_scale_columns(k_scale, block_tables),
            _gathered_scale_columns(v_scale, block_tables),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, bpr + 1),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h_kv, rows, d), jnp.float32),
            pltpu.VMEM((h_kv, rows, 1), jnp.float32),
            pltpu.VMEM((h_kv, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _verify_kernel, block_size=bs, h_kv=h_kv, d=d, w=w, n_rep=n_rep,
            scale=scale, softcap=softcap, quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_kv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_verify",
    )(block_tables, pos.astype(jnp.int32), *args)
    out = out.reshape(b, h_kv, n_rep, w, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, w, h, d)


# ---------------------------------------------------------- fused sampling
def _float_key(x):
    """Order-preserving map f32 -> uint32: ``a < b  iff  key(a) < key(b)``
    (total order; -0.0 keys just below +0.0, which float comparisons on the
    selected *values* downstream never observe). Positive floats flip the
    sign bit, negative floats flip every bit."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    neg = (u >> 31) == 1
    return jnp.where(neg, ~u, u | jnp.uint32(0x80000000))


_SAMPLE_ROWS = 8  # rows per program: one f32 sublane tile


def _sample_kernel(temp_ref, tk_ref, tp_ref, logits_ref, noise_ref, out_ref,
                   *, vocab):
    # Semantics contract: engine._filter_logits + engine._sample_rows, every
    # reduction per row. The reference sorts the row and derives (a) the
    # k-th largest value `kth` and (b) the top-p cutoff `sorted_f[c-1]`
    # where c = #(exclusive-cumsum(softmax(top-k-kept, sorted)) < p); its
    # final rule is value-level: keep x iff [~k_on or x >= kth] and
    # [x >= cutoff]. Both thresholds are recovered here WITHOUT a sort:
    #   * kth — exact k-th order statistic by 32-step binary search over
    #     the monotone uint32 float image (count(key >= t) >= k).
    #   * cutoff — `x >= cutoff  iff  S(x) < p * Z` for every top-k-kept x,
    #     where S(x) = sum of exp(y - m) over kept y > x and Z the kept
    #     normalizer (everything strictly greater than a kept value is
    #     itself kept, so S needs no top-k correction). This is the
    #     reference rule exactly, ties included: cutoff = min{kept v :
    #     mass-strictly-above(v) < p}, and both sides of the iff are
    #     monotone steps in x changing only at element values. The binary
    #     search finds the minimal float key satisfying S < p*Z; summation
    #     order differs from the reference cumsum only in last-ulp rounding
    #     AT the p boundary (measure-zero on real logits).
    t = temp_ref[...]    # (R, 1) per-row knobs
    tk = tk_ref[...]
    tp = tp_ref[...]
    x = logits_ref[...]  # (R, V) f32
    noise = noise_ref[...]
    iota = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    neg_inf = jnp.float32(-jnp.inf)

    def row_sum(a):
        return jnp.sum(a, axis=-1, keepdims=True)

    def row_max(a):
        return jnp.max(a, axis=-1, keepdims=True)

    def first_index_of(a, m):
        return jnp.min(jnp.where(a == m, iota, vocab), axis=-1, keepdims=True)

    # greedy = argmax of the RAW logits (first max index), per _sample_rows
    greedy = first_index_of(x, row_max(x))

    safe_t = jnp.where(t > 0, t, jnp.float32(1.0))
    scaled = x / safe_t
    key = _float_key(scaled)

    k_on = jnp.logical_and(tk > 0, tk < vocab)
    k_eff = jnp.clip(tk, 1, vocab)
    # maximal key with count(key >= key0) >= k_eff == key of the k-th
    # largest element (count() only steps at element keys)
    kkey = jnp.zeros_like(tk, dtype=jnp.uint32)
    for bit in range(31, -1, -1):
        cand = kkey | jnp.uint32(1 << bit)
        cnt = row_sum(jnp.where(key >= cand, 1, 0))
        kkey = jnp.where(cnt >= k_eff, cand, kkey)
    kth = row_max(jnp.where(key == kkey, scaled, neg_inf))
    keep_k = jnp.logical_or(jnp.logical_not(k_on), scaled >= kth)

    # top-p over the top-k survivors' distribution (reference: softmax of
    # the SORTED top-k row, so Z counts exactly k_eff entries — ties at
    # kth beyond k_eff are kept by the filter but excluded from Z)
    m_s = row_max(scaled)
    e = jnp.exp(scaled - m_s)
    cnt_gt = row_sum(jnp.where(scaled > kth, 1, 0))
    z_k = (row_sum(jnp.where(scaled > kth, e, 0.0))
           + (k_eff - cnt_gt).astype(jnp.float32) * jnp.exp(kth - m_s))
    z = jnp.where(k_on, z_k, row_sum(e))
    p_on = tp < 1.0
    pz = jnp.where(p_on, tp, jnp.float32(1.0)) * z
    # minimal key u0 with S(u0) < p*Z, via maximal key with S >= p*Z
    u1 = jnp.zeros_like(tk, dtype=jnp.uint32)
    for bit in range(31, -1, -1):
        cand = u1 | jnp.uint32(1 << bit)
        s_above = row_sum(jnp.where(key > cand, e, 0.0))
        u1 = jnp.where(s_above >= pz, cand, u1)
    s_at_u1 = row_sum(jnp.where(key > u1, e, 0.0))
    u0 = jnp.where(s_at_u1 >= pz, u1 + jnp.uint32(1), u1)
    keep_p = jnp.logical_or(jnp.logical_not(p_on), key >= u0)

    final = jnp.where(jnp.logical_and(keep_k, keep_p), scaled, neg_inf)
    # categorical == argmax(final + gumbel) with the caller's per-row noise
    g = final + noise
    sampled = first_index_of(g, row_max(g))
    out_ref[...] = jnp.where(t > 0, sampled, greedy)


def fused_sample(
    logits: jax.Array,
    noise: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused sampling epilogue: temperature / top-k / top-p filter +
    categorical draw in one kernel, eight rows per grid step.

    ``logits`` (S, V) f32 raw logits, ``noise`` (S, V) f32 per-row Gumbel
    noise — generate it as ``vmap(lambda k: jax.random.gumbel(k, (V,),
    jnp.float32))(subkeys)`` so the draw is bitwise what
    ``vmap(jax.random.categorical)(subkeys, filtered)`` returns (categorical
    IS argmax(logits + gumbel(key)); in-kernel TPU PRNG has no interpret
    lowering). ``temperature``/``top_k``/``top_p`` are the (S,) per-row
    knobs with `engine._sample_rows` semantics: temperature <= 0 is greedy
    argmax over the RAW logits. Returns (S,) int32 token ids.

    A row of a 2-D array is not a tile the TPU lowering accepts, so each
    program takes ``_SAMPLE_ROWS`` whole rows (the vocabulary axis stays
    whole, whatever its size) and ``S`` is padded up to a multiple of that
    with greedy rows that are dropped again.
    """
    s, v = logits.shape
    interpret = _resolve_interpret(interpret)
    r = _SAMPLE_ROWS
    pad = -s % r

    def rows(a, dtype):
        a = a.astype(dtype)
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a

    def knob(a, dtype):
        return rows(a, dtype)[:, None]

    row_spec = pl.BlockSpec((r, v), lambda i: (i, 0))
    knob_spec = pl.BlockSpec((r, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_sample_kernel, vocab=v),
        grid=((s + pad) // r,),
        in_specs=[knob_spec, knob_spec, knob_spec, row_spec, row_spec],
        out_specs=knob_spec,
        out_shape=jax.ShapeDtypeStruct((s + pad, 1), jnp.int32),
        # two double-buffered inputs and the filter's live rows: a 152k
        # vocabulary needs 27.7 MB of the chip's 128, past the 16 MB default
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, 8 * r * v * 4)
        ),
        interpret=interpret,
        name="fused_sample",
    )(
        knob(temperature, jnp.float32),
        knob(top_k, jnp.int32),
        knob(top_p, jnp.float32),
        rows(logits, jnp.float32),
        rows(noise, jnp.float32),
    )
    return out[:s, 0]
