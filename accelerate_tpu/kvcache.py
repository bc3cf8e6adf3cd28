"""Paged KV-cache subsystem: block pool, block tables, copy-on-write prefix
caching, and optional int8 KV for the decode paths.

The continuous engine's original KV store is a dense arena ``(layers, slots,
max_len, kv_heads, head_dim)``: every slot reserves its worst case, so HBM —
not compute — caps concurrency (ROADMAP open item 1). This module replaces
that store with the vLLM/Orca-class paged design while keeping the engine's
bounded-program discipline intact (two jitted programs per config; three
when speculative decoding adds its ``verify_step``):

* **Block pool + block tables** — one shared device pool ``(layers,
  num_blocks, block_size, kv_heads * head_dim)``; each slot owns a row of a
  host-side block table mapping its logical positions to pool blocks. Decode
  gathers a slot's blocks into the dense per-layer view the model attention
  already consumes (``pool[layer, tables]`` + reshape), writes the new token
  column back with one scatter, and prefill writes its rung's blocks with
  one scatter more. Tables ride into the compiled programs as
  *traced operands* (values change, shapes don't), so a paged engine still
  dispatches one decode program and one prefill program a rung per config.
* **Admission by free blocks, not max_len** — a request needs
  ``ceil((prompt + budget) / block_size)`` blocks, so short requests stop
  paying long requests' reservation. The engine/server gate admission on
  :meth:`PagedBlockPool.can_admit` instead of slot count alone.
* **Copy-on-write prefix caching** — full prompt blocks register in a
  host-side registry keyed by the exact block-aligned prompt prefix bytes;
  a request whose prefix matches takes a refcount on the existing blocks
  instead of new ones (system prompts dedup across every concurrent user).
  Refcounts release on retirement; zero-ref registered blocks park in an
  LRU "cached" tier that still serves hits and is evicted only on demand.
  A sharer's prefill does not write the shared blocks (their table entries
  go to the null block for its scatter): causal attention makes prefix KV
  depend only on prefix tokens, but a sharer padded to another rung runs
  another program, which may round otherwise.
* **int8 KV** — pool stored as int8 plus per-(layer, block, position) f32
  scales; quantized on write (prefill blocks and the decode column) and
  dequantized inside the compiled step right before attention. Halves-to-
  quarters pool HBM at a bounded, deterministic accuracy cost.

The pool's layout, and who may slice it. A pool leaf is ``(layers,
num_blocks, block_size, kv_heads * head_dim)``: the two head axes are one, so
that a block is a ``(block_size, kv_heads * head_dim)`` tile the chip keeps
row-major and unpadded whatever the head size (with ``head_dim`` 64 as a
minor axis of its own the TPU lays the pool out with ``num_blocks`` minor:
every kernel call then transposed a layer's slice, and every one-block write
swept the pool; PERF.md, PR 28). Programs take the pool donated, update it in
place and hand it back in the same layout. Inside a program nobody slices it
by layer: a layer loop carries the pool whole (or closes over it when it only
reads), and a layer reaches its part by index: :class:`PagedKVLayout`'s ops
scatter and gather at ``[layer, block, offset]``, the Pallas kernels walk
``tables + layer * num_blocks`` over the pool flattened to ``(layers *
num_blocks, block_size, kv_heads * head_dim)``. Whole blocks move in and out
at ``[:, ids]`` (prefill, spill, restore, transfer). A scan that took the pool
as ``xs`` and gave it back as ``ys`` would copy it every step.

Safety invariants (the reasons slot recycling cannot corrupt KV):

* Block 0 is the reserved **null block**: vacant/retired slots' table rows
  point at it, so the unconditional per-step KV writes of masked slots land
  in a garbage sink nobody ever attends to (``k_pos <= pos`` masking keeps
  every unallocated position out of attention with exp-underflow-exact
  zero weights — see ``NEG_INF`` in ops/attention.py).
* A live slot writes position ``p`` in the same program that first attends
  it, so blocks recycled from a previous occupant never leak stale KV.
* Decode writes happen at ``pos >= prompt_len`` while registered (shared)
  blocks only cover positions ``< floor(prompt_len/bs)*bs``, so shared
  content is never written after registration — COW without copies.

The seam to a model's step. A family's block (models/llama.py, gpt2.py,
lfm2.py) computes norms, projections and rope and hands the rotated ``q, k, v``
of a layer to :func:`attend_step` (one new position a row: write, attend,
return the updated store) or :func:`attend_window` (a window over history with
the store read-only: verify, chunked prefill); :func:`scan_layers` is the
layer loop of the families whose layers are stacked. Behind these three, and
nowhere else: whether the store is the dense arena, the pool or the int8
``{"q", "s"}`` pool; whether the pool's blocks are gathered to a dense view,
attended (``ops/attention.py::cache_attention``) and the column committed back,
or the column committed first and the Pallas kernel run over the pool in place;
that a sliding window downgrades the kernel to the reference; what rides a
loop's carry. A family that changes what a cache holds changes this file and
its own block.

Two kinds of state. A family says how many of its layers keep keys and values
and how large a head is (``config.serving_family()``, models/family.py): the
arena's and the pool's leading axis is that count, not the model's depth. A
family whose other layers keep a fixed-size recurrent state a sequence (a short
convolution's last inputs) gets a third leaf beside ``"k"`` and ``"v"``:
``"recurrent"``, ``(recurrent layers, slots, *state shape)``, one row a slot
whatever the backend. ``prefill_write`` stores what the prefill took at the
prompt's true last position, the decode step advances it, and ``release``
clears nothing: the next occupant's prefill overwrites the row before anything
reads it. A prefix-cache hit shares blocks of keys and values only; the sharer's
prefill still runs over its whole prompt (it leaves the shared blocks as they
are), so its recurrent state is its own. ``hbm_bytes``, ``live`` and
``reserved`` tokens count keys and values; ``stats()["recurrent_state_bytes"]``
reports the recurrent rows beside them.

A latent row. A family whose values are a prefix of its keys' own rows (latent
attention: one compressed row a token a layer under every query head) says so
with ``value_dim`` (models/family.py): the cache then has ONE leaf, ``"k"``, of
``kv_heads * head_dim`` values a position, and no ``"v"``; the store a step
hands the seam is ``(rows, None)``, the reference attend takes its values as
``rows[..., :value_dim]`` of the dense view, and the kernel copies each live row
out of the pool once (``paged_flash_decode(..., v_pool=None, value_dim=...)``).
The latent is never expanded in the arena or in the pool. ``paged_int8`` (one
scale a position over a row whose two parts differ in size) and the host tier
refuse such a family when the engine is built.

Backends:

``dense``       today's arena semantics behind the same interface
``paged``       block pool + tables + COW prefix cache
``paged_int8``  same, int8 pool + per-block-position scales
"""

from __future__ import annotations

import collections
import functools
import queue
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .logging import get_logger
from .ops.attention import cache_attention

logger = get_logger(__name__)

__all__ = [
    "KVCacheBackend",
    "DenseKVBackend",
    "PagedKVBackend",
    "PagedBlockPool",
    "PagedKVLayout",
    "HostKVTier",
    "make_kv_backend",
    "attend_step",
    "attend_window",
    "scan_layers",
    "kv_quantize",
    "kv_dequantize",
    "KV_BACKENDS",
]

KV_BACKENDS = ("dense", "paged", "paged_int8")

_NULL_BLOCK = 0  # reserved garbage sink; never allocated, never attended


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ------------------------------------------------------------------ int8 ops
def kv_quantize(x):
    """Symmetric int8 quantization with one scale per leading position:
    ``x`` is ``(..., kv_heads, head_dim)``; the amax reduces over the last
    two axes so every (layer, block, position) gets its own scale — the
    per-block-scale granularity the int8 KV pool stores. Deterministic
    (pure round/clip), so identical inputs quantize to identical bytes —
    the property shared-prefix COW re-writes rely on."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-1, -2)), 1e-6)
    scale = (amax / 127.0).astype(jnp.float32)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None, None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    """Inverse of :func:`kv_quantize`: ``q (..., kv_heads, head_dim)`` int8
    times per-position ``scale (...)`` back to ``dtype``."""
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


# --------------------------------------------------------------- device side
def _merge_heads(x):
    """``(..., kv_heads, head_dim)`` -> ``(..., kv_heads * head_dim)``, the
    pool's lane axis."""
    return x.reshape(*x.shape[:-2], -1)


def _split_heads(x, head_dim: int):
    """The inverse: ``(..., kv_heads * head_dim)`` -> ``(..., kv_heads,
    head_dim)``."""
    return x.reshape(*x.shape[:-1], -1, head_dim)


class PagedKVLayout:
    """Device-side view/commit ops over the pool, closed over the (traced)
    block tables. Built *inside* a jitted program each dispatch — tables are
    operands, not constants, so table churn never recompiles.

    Every op takes the whole pool ``(L, num_blocks, bs, kvh * hd)`` and the
    (traced) ``layer`` it works on, and reaches that layer by index, so the
    layer loop carries the pool whole (module docstring, "The pool's
    layout"); ``layer=None`` takes one layer's ``(num_blocks, bs, kvh * hd)``.

    The reference attend path consumes a dense ``(B, max_len, kvh, hd)``
    cache: :meth:`view` gathers it from the pool (dequantizing int8),
    :meth:`commit` extracts the single new column written at ``pos`` and
    scatters it back (quantizing int8). Its callers are :func:`attend_step`
    and :func:`attend_window`, the seam a model's step goes through — one KV
    story for dense and paged."""

    def __init__(self, tables, block_size: int, compute_dtype, head_dim: int,
                 attention_impl: str = "reference"):
        self.tables = tables  # (B, blocks_per_row) int32, traced
        self.block_size = block_size
        self.compute_dtype = compute_dtype
        self.head_dim = head_dim
        # "reference": attend_step gathers view() and commits after
        # attending; "pallas": it commits the new column first
        # (commit_column) and the fused flash-decode kernel walks the tables
        # itself — no dense view is ever materialized (ops/paged_decode.py)
        self.attention_impl = attention_impl
        if attention_impl == "pallas":
            from .ops.paged_decode import decode_chunk_runs

            # how each chunk of a latent pool's walk breaks into runs of the
            # pool, made once here, outside the layer loop (dead code where the
            # step walks a per-head pool, whose blocks are copied one by one)
            self.chunk_runs = decode_chunk_runs(tables, block_size)

    @jax.named_scope("kv.gather")
    def view(self, pool, layer=None):
        """Gather one layer of the pool into the dense per-slot view:
        ``(L, num_blocks, bs, kvh * hd)`` at ``layer`` (or the int8
        ``{"q","s"}`` pair) → ``(B, blocks_per_row * bs, kvh, hd)``.
        Unallocated table entries gather the null block — masked out of
        attention by ``k_pos <= pos``."""
        at = (self.tables,) if layer is None else (layer, self.tables)
        if isinstance(pool, dict):
            dense = kv_dequantize(
                _split_heads(pool["q"][at], self.head_dim), pool["s"][at],
                self.compute_dtype,
            )
        else:
            dense = _split_heads(pool[at], self.head_dim)
        b, bpr, bs, kvh, hd = dense.shape
        return dense.reshape(b, bpr * bs, kvh, hd).astype(self.compute_dtype)

    def commit(self, pool, view, pos, layer=None):
        """Scatter the one new column the decode layer wrote at ``pos``
        back into the pool. ``pos`` is a traced (B,) vector (engine
        slots) or scalar (the fused generate scan). Ghost slots (retired /
        vacant) carry null-block table entries, so their unconditional
        masked-step writes land in the garbage sink."""
        if jnp.ndim(pos) == 0:
            pos = jnp.broadcast_to(pos, (self.tables.shape[0],))
        col = jnp.take_along_axis(view, pos[:, None, None, None], axis=1)
        return self.commit_column(pool, col, pos, layer)

    @jax.named_scope("kv.scatter")
    def commit_column(self, pool, col, pos, layer=None):
        """Scatter one freshly-computed K (or V) column ``col`` (B, 1, kvh,
        hd) at ``pos`` directly into the pool at ``[layer, block, offset]``
        — the Pallas decode path's commit-BEFORE-attend: the kernel then
        reads the column back from the pool (store→load identity in f32; one
        bounded quantization for int8), so no dense view is ever gathered.
        Ghost slots (retired / vacant) carry null-block table entries, so
        their unconditional masked-step writes land in the garbage sink."""
        if jnp.ndim(pos) == 0:
            pos = jnp.broadcast_to(pos, (self.tables.shape[0],))
        col = col[:, 0]
        blk = jnp.take_along_axis(
            self.tables, (pos // self.block_size)[:, None], axis=1
        )[:, 0]
        off = pos % self.block_size
        at = (blk, off) if layer is None else (layer, blk, off)
        if isinstance(pool, dict):
            q, s = kv_quantize(col)
            return {
                "q": pool["q"].at[at].set(_merge_heads(q)),
                "s": pool["s"].at[at].set(s),
            }
        return pool.at[at].set(_merge_heads(col).astype(pool.dtype))

    @jax.named_scope("kv.scatter")
    def commit_window(self, pool, window, pos, count):
        """Scatter the first ``count[b]`` columns of a speculative-verify
        window into the pool, every layer at once: ``window`` is
        ``(L, B, W, kvh, hd)`` holding the window K (or V) rows at positions
        ``pos .. pos+W-1``, ``count`` (B,) the per-slot accepted length.
        Rejected/padded columns (``j >= count``) and positions past the
        row's table coverage route to the null block — a failed speculation
        "rewinds" by simply never being committed, so block tables and
        refcounts need no rollback path. Like decode commits, windows start
        at ``pos >= prompt_len``, so registered COW prefix blocks are never
        written here."""
        bs = self.block_size
        w = window.shape[2]
        bpr = self.tables.shape[1]
        j = jnp.arange(w, dtype=jnp.int32)[None, :]
        abs_pos = pos[:, None] + j  # (B, W)
        valid = (j < count[:, None]) & (abs_pos < bpr * bs)
        blk = jnp.take_along_axis(
            self.tables, jnp.clip(abs_pos // bs, 0, bpr - 1), axis=1
        )
        blk = jnp.where(valid, blk, _NULL_BLOCK)
        off = abs_pos % bs
        if isinstance(pool, dict):
            q, s = kv_quantize(window)  # per-(layer, slot, position) scales
            return {
                "q": pool["q"].at[:, blk, off].set(_merge_heads(q)),
                "s": pool["s"].at[:, blk, off].set(s),
            }
        return pool.at[:, blk, off].set(_merge_heads(window).astype(pool.dtype))


# ------------------------------------------------- the seam to a model's step
def _write_at(cache, kv, pos):
    """Write one new position's K (or V) rows into a (B, max_len, H, D)
    cache. Scalar ``pos`` writes every row at the same position (the fused
    generate scan); a (B,) ``pos`` scatters each row at its own position
    (continuous-batching slots, each mid-way through its own sequence)."""
    kv = kv.astype(cache.dtype)
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(cache, kv, (0, pos, 0, 0))
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice(c, n, (p, 0, 0))
    )(cache, kv, pos)


def _write_window(cache, kv, pos):
    """Write a W-position window of K (or V) rows into a (B, S_cache, H, D)
    cache at per-row start positions ``pos`` (B,). Unlike :func:`_write_at`'s
    ``dynamic_update_slice`` (which CLAMPS start indices, silently shifting an
    overhanging write onto live columns), this scatters each position
    independently and DROPS any that fall past the cache length — required
    for verify windows whose padded tail can legally overhang the arena."""
    kv = kv.astype(cache.dtype)
    w = kv.shape[1]

    def one(c, n, p):
        idx = p + jnp.arange(w, dtype=jnp.int32)
        return c.at[idx].set(n, mode="drop")

    return jax.vmap(one)(cache, kv, pos)


def _kernel_attends(layout, window) -> bool:
    """Whether the Pallas kernels (ops/paged_decode.py) attend: opted in on
    the layout, and no sliding window, which they do not mask (the engine
    downgrades such a config up front and says so; this is the same rule
    where the choice is made)."""
    return layout is not None and layout.attention_impl == "pallas" and window is None


def _run_kernel(kernel, q, k_pool, v_pool, *operands, **kw):
    """An int8 pool is a ``{"q", "s"}`` pair: values and per-position scales."""
    if isinstance(k_pool, dict):
        return kernel(q, k_pool["q"], v_pool["q"], *operands,
                      k_scale=k_pool["s"], v_scale=v_pool["s"], **kw)
    return kernel(q, k_pool, v_pool, *operands, **kw)


def _attend_dense(write, layout, store, layer, q, k, v, pos, value_dim=None, **attention):
    """The reference attend: each of ``store``'s two as one layer's dense
    ``(B, S, kv_heads, head_dim)`` (gathered from the pool, the arena's slice,
    or with ``layer`` None the arena's layer as handed in), ``k`` / ``v``
    written into it by ``write``, and the one attention over it. Returns
    ``(out, dense keys, dense values)``. With ``value_dim`` the store has no
    values of its own: they are the first ``value_dim`` columns of the keys'
    rows, and ``dense values`` is None."""
    def dense(which, new):
        if layout is not None:
            view = layout.view(which, layer)
        else:
            view = which if layer is None else which[layer]
        return write(view, new, pos)

    dense_k = dense(store[0], k)
    if value_dim is not None:
        return cache_attention(q, dense_k, dense_k[..., :value_dim], pos, **attention), dense_k, None
    dense_v = dense(store[1], v)
    return cache_attention(q, dense_k, dense_v, pos, **attention), dense_k, dense_v


def attend_step(layout, store, layer, q, k, v, pos, *, scale=None, softcap=None,
                window=None, sliding=None, value_dim=None):
    """One new position a row: write ``k`` / ``v`` (B, 1, kv_heads, head_dim,
    rotated) at ``pos`` (a traced scalar or (B,)) and attend ``q`` over
    positions ``<= pos``. Returns ``(out (B, 1, heads, head_dim), store)``.

    ``store`` is the ``(keys, values)`` pair and ``layer`` the index into it:
    with a ``layout`` the whole pools; with ``layout=None`` the whole dense
    arena ``(layers, B, S, kv_heads, head_dim)``, or with ``layer=None`` one
    layer's own ``(B, S, kv_heads, head_dim)``. Three paths: the arena is
    written and attended; the pool's blocks are gathered to a dense view,
    attended, and the new column scattered back; or the column is committed
    FIRST and the kernel walks the block tables over the pool in place (store
    then load is exact in float; an int8 pool pays the one bounded
    quantization every committed position pays). ``scale``, ``softcap``,
    ``window``, ``sliding``: :func:`~accelerate_tpu.ops.attention.cache_attention`'s.

    A latent store (``value_dim``, module docstring): ``store`` is ``(rows,
    None)``, ``k`` the new row ``(B, 1, 1, width)`` and ``v`` None; ``q`` is as
    wide as a row and the result ``value_dim`` wide a head."""
    keys, values = store
    latent = value_dim is not None
    if _kernel_attends(layout, window):
        from .ops.paged_decode import paged_flash_decode

        keys = layout.commit_column(keys, k, pos, layer)
        if not latent:
            values = layout.commit_column(values, v, pos, layer)
        rows = pos if jnp.ndim(pos) else jnp.broadcast_to(pos, q.shape[:1])
        out = _run_kernel(paged_flash_decode, q, keys, values, layout.tables, rows,
                          scale=scale, softcap=softcap, layer=layer, value_dim=value_dim,
                          chunk_runs=layout.chunk_runs if latent else None)
        return out.astype(q.dtype), (keys, values)
    out, dense_k, dense_v = _attend_dense(
        _write_at, layout, store, layer, q, k, v, pos, value_dim,
        scale=scale, softcap=softcap, window=window, sliding=sliding,
    )

    def committed(which, dense):
        if dense is None:  # a latent store keeps no values
            return None
        if layout is not None:
            return layout.commit(which, dense, pos, layer)
        return dense if layer is None else which.at[layer].set(dense)

    return out, (committed(keys, dense_k), committed(values, dense_v))


def attend_window(layout, store, layer, q, k, v, pos, *, scale=None, softcap=None,
                  window=None, sliding=None):
    """A window of W positions a row at ``pos .. pos+W-1`` (``pos`` (B,)) over
    history plus the window itself, causally: speculative verify and chunked
    prefill. ``store`` (as :func:`attend_step`'s) is READ-ONLY: the window's
    keys and values go into a temporary copy of the dense view (or straight
    to the kernel, which reads history ``k_pos < pos`` from the pool and
    attends the window in registers), positions past the cache's length are
    dropped, never clamped onto a live column. Returns ``(out, (k, v))``: the
    window's own keys and values, of which the caller commits the prefix it
    accepts (``commit_window``); what it rejects never existed."""
    keys, values = store
    if _kernel_attends(layout, window):
        from .ops.paged_decode import paged_flash_verify

        out = _run_kernel(paged_flash_verify, q, keys, values, k, v, layout.tables, pos,
                          scale=scale, softcap=softcap, layer=layer)
        return out.astype(q.dtype), (k, v)
    out, _, _ = _attend_dense(
        _write_window, layout, store, layer, q, k, v, pos,
        scale=scale, softcap=softcap, window=window, sliding=sliding,
    )
    return out, (k, v)


def scan_layers(attend_op, layout, block, x, cache, layers, *flags):
    """The layer loop of a step over stacked ``layers``. ``block(x,
    layer_params, attend, *flags)`` returns ``(x, kept)``, where ``attend`` is
    ``attend_op`` (:func:`attend_step` or :func:`attend_window`) bound to the
    layout, the store and the layer, and ``kept`` is what it returned second.
    Returns ``(x, {"k", "v"})`` of what was kept: the new cache, or the
    windows' keys and values stacked by layer.

    The dense arena rides the scan as ``xs`` (and back as ``ys``), a layer a
    step. A pool never does: it goes whole, in the carry beside ``x`` when the
    step writes it (the program's donated pool is updated in place and handed
    back) or closed over when it is only read, and the scan steps over
    ``(layer params, layer index[, flags])`` (module docstring, "The pool's
    layout")."""
    if layout is None:
        def body(x, inputs):
            layer_params, keys, values, *rest = inputs
            attend = functools.partial(attend_op, None, (keys, values), None)
            return block(x, layer_params, attend, *rest)

        x, (k, v) = lax.scan(body, x, (layers, cache["k"], cache["v"], *flags))
        return x, {"k": k, "v": v}
    n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    xs = (layers, jnp.arange(n_layers, dtype=jnp.int32), *flags)
    if attend_op is attend_window:
        def body(x, inputs):
            layer_params, layer, *rest = inputs
            attend = functools.partial(attend_op, layout, (cache["k"], cache["v"]), layer)
            return block(x, layer_params, attend, *rest)

        x, (k, v) = lax.scan(body, x, xs)
        return x, {"k": k, "v": v}

    def body(carry, inputs):
        x, *store = carry
        layer_params, layer, *rest = inputs
        attend = functools.partial(attend_op, layout, tuple(store), layer)
        x, (keys, values) = block(x, layer_params, attend, *rest)
        return (x, keys, values), None

    (x, k, v), _ = lax.scan(body, (x, cache["k"], cache["v"]), xs)
    return x, {"k": k, "v": v}


# ----------------------------------------------------------- host spill tier
class HostKVTier:
    """Pinned host-RAM spill tier below the pool's zero-ref cached-LRU
    (docs/serving.md "Long-context serving"). Evicted *registered* prefix
    blocks land here (payload exactly as the pool stores it: f32, or int8
    bytes + per-position f32 scales) instead of dying, keyed by the same
    exact block-aligned prefix bytes as the device registry — so a host hit
    restores the identical bytes a never-evicted block would have held
    (bitwise in f32; the int8 payload dequantizes within the committed
    4.0e-3·amax bound because it IS the original quantization).

    Content-addressed keys make staleness structurally impossible: a key is
    the full token prefix, and deterministic quantization maps identical
    prefixes to identical bytes, so a "stale" host block can only exist
    across a model/config swap — which resets the engine and clears the
    tier (docs/fault_tolerance.md failure-mode table).

    Thread contract: ``insert`` is called from the backend's background
    spill thread, ``lookup``/``stats``/``clear`` from the engine (serving
    worker) thread — every mutation holds ``_lock``. Capacity is enforced
    in blocks (``capacity_bytes // block_bytes``), LRU-evicted on insert;
    the tier never grows past ``capacity_bytes`` of host RAM."""

    def __init__(self, capacity_bytes: int, block_bytes: int):
        if capacity_bytes < 0:
            raise ValueError(
                f"capacity_bytes must be >= 0, got {capacity_bytes}"
            )
        if block_bytes < 1:
            raise ValueError(f"block_bytes must be >= 1, got {block_bytes}")
        self.capacity_bytes = capacity_bytes
        self.block_bytes = block_bytes
        self.capacity_blocks = capacity_bytes // block_bytes
        self._blocks: "collections.OrderedDict[bytes, Any]" = collections.OrderedDict()
        self._lock = threading.Lock()
        self.spill_blocks = 0
        self.spill_bytes = 0
        self.restore_hits = 0
        self.restore_bytes = 0
        self.restore_misses = 0
        self.dropped = 0  # LRU-evicted out of the tier (truly dead now)

    def __len__(self) -> int:
        with self._lock:
            return len(self._blocks)

    def bytes_used(self) -> int:
        with self._lock:
            return len(self._blocks) * self.block_bytes

    def insert(self, key: bytes, payload: Any) -> bool:
        """Insert one spilled block (host numpy payload). Returns False when
        the tier has zero capacity (spill accounting still advances so the
        eviction pressure stays observable)."""
        with self._lock:
            self.spill_blocks += 1
            self.spill_bytes += self.block_bytes
            if self.capacity_blocks < 1:
                self.dropped += 1
                return False
            while len(self._blocks) >= self.capacity_blocks:
                self._blocks.popitem(last=False)
                self.dropped += 1
            self._blocks[key] = payload
            self._blocks.move_to_end(key)
            return True

    def lookup(self, key: bytes) -> Optional[Any]:
        """Host-tier probe; a hit refreshes LRU recency. Hit/restore
        counters advance at *restore* time (see ``count_restore``) so a
        probe that is never consumed doesn't inflate the win."""
        with self._lock:
            payload = self._blocks.get(key)
            if payload is None:
                self.restore_misses += 1
                return None
            self._blocks.move_to_end(key)
            return payload

    def count_restore(self, n_blocks: int) -> None:
        with self._lock:
            self.restore_hits += n_blocks
            self.restore_bytes += n_blocks * self.block_bytes

    def hot_keys(self, n: int = 8) -> List[bytes]:
        """Most-recently-used prefix keys — the replication candidates for
        fleet-wide hot-prefix fan-out. MRU order (hottest first)."""
        with self._lock:
            return list(reversed(self._blocks.keys()))[: max(0, n)]

    def contains(self, key: bytes) -> bool:
        """Membership probe with NO stat side effects (``lookup`` counts a
        miss and refreshes LRU) — the hot-prefix replicator's dedup check."""
        with self._lock:
            return key in self._blocks

    def clear(self) -> None:
        with self._lock:
            self._blocks.clear()

    def stats(self) -> dict:
        with self._lock:
            return {
                "host_tier_capacity_bytes": self.capacity_bytes,
                "host_tier_blocks": len(self._blocks),
                "host_tier_bytes": len(self._blocks) * self.block_bytes,
                "spill_blocks": self.spill_blocks,
                "spill_bytes": self.spill_bytes,
                "restore_hits": self.restore_hits,
                "restore_bytes": self.restore_bytes,
                "restore_misses": self.restore_misses,
                "host_tier_dropped": self.dropped,
            }


# ------------------------------------------------------------ host block pool
class PagedBlockPool:
    """Host-side allocator for the device block pool: free list, refcounts,
    per-slot block-table rows, and the COW prefix registry.

    Single-threaded by design — the serving worker owns the engine. Block
    states:

    * **free** — set in the free map, content meaningless.
    * **active** — refcount >= 1; owned by >= 1 live slots.
    * **cached** — refcount 0 but still registered under its prompt-prefix
      key; serves prefix hits across *sequential* waves and is evicted LRU
      only when the free list runs dry (so "free capacity" = free + cached).

    The registry keys are the exact prefix bytes ``prompt[: (d+1) *
    block_size]`` — no hash collisions, and a lookup walks depths 0, 1, 2…
    stopping at the first miss, so evicting a shallow block simply orphans
    (and stops serving) its deeper extensions.

    A request's private blocks are handed out as two ascending runs of free
    blocks where the free map holds them: the blocks it registers from the top
    of the pool, the rest from the bottom (each split at the decode walk's
    chunk boundaries, ``run_blocks`` blocks, where no run is that long), so
    that the walk copies a chunk's live blocks with a few descriptors
    (``ops/paged_decode.py``) and blocks left cached by a release do not cut
    the free ones into short runs. Cached blocks are evicted only for what the
    free ones lack, LRU first, before the runs are chosen: the same blocks a
    pool that took them one at a time would evict."""

    def __init__(self, *, num_blocks: int, block_size: int, slots: int,
                 blocks_per_row: int, run_blocks: int = 1):
        if num_blocks < 2:
            raise ValueError(
                f"pool needs >= 2 blocks (1 is the reserved null block), "
                f"got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.slots = slots
        self.blocks_per_row = blocks_per_row
        self.run_blocks = run_blocks
        # host-tier spill interception: when set, _evict_one hands every
        # still-registered LRU victim's (key, block) to the owner BEFORE
        # the registry entry dies, so the backend can snapshot the device
        # bytes ahead of the block's reallocation (engine dispatches the
        # overwriting prefill only after acquire returns)
        self.spill_fn: Optional[Callable[[bytes, int], None]] = None
        self.reset()

    # -------------------------------------------------------------- lifecycle
    def reset(self) -> None:
        self._is_free = np.ones(self.num_blocks, dtype=bool)
        self._is_free[_NULL_BLOCK] = False
        self._ref = np.zeros(self.num_blocks, dtype=np.int64)
        self._registry: Dict[bytes, int] = {}
        self._key_of: Dict[int, bytes] = {}
        self._cached: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        self._rows: List[List[int]] = [[] for _ in range(self.slots)]
        # chunked-prefill COW safety: fresh prompt blocks of a PREFILLING
        # slot must not serve prefix hits until their content exists, so
        # their registrations are parked here and promoted at completion
        self._deferred: Dict[int, List[Tuple[bytes, int]]] = {}
        self.tables = np.zeros((self.slots, self.blocks_per_row), dtype=np.int32)
        self.prefix_hits = 0
        self.prefix_misses = 0

    # ------------------------------------------------------------- accounting
    def blocks_needed(self, prompt_len: int, budget: int) -> int:
        # budget tokens occupy positions [prompt_len, prompt_len+budget):
        # the last decode write lands at prompt_len+budget-1 (done slots
        # keep re-writing their frozen final position until retired)
        return _ceil_div(prompt_len + budget, self.block_size)

    def max_request_blocks(self) -> int:
        return self.num_blocks - 1  # everything but the null block

    def free_blocks(self) -> int:
        """Allocatable capacity: truly free + LRU-evictable cached."""
        return self._free_count() + len(self._cached)

    def active_blocks(self) -> int:
        return int((self._ref > 0).sum())

    def _shared_prefix(self, prompt: np.ndarray) -> List[int]:
        """Registry hits for ``prompt``'s full blocks, deepest-first walk
        stopping at the first miss. Read-only (used by both the admission
        probe and acquire)."""
        bs = self.block_size
        hits: List[int] = []
        for depth in range(len(prompt) // bs):
            blk = self._registry.get(prompt[: (depth + 1) * bs].tobytes())
            if blk is None:
                break
            hits.append(blk)
        return hits

    def can_admit(self, prompt: np.ndarray, budget: int) -> bool:
        """True when ``acquire`` for this request would succeed right now.
        Cached blocks the request would *hit* are not double-counted as
        evictable capacity."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        hits = self._shared_prefix(prompt)
        needed = self.blocks_needed(len(prompt), budget) - len(hits)
        evictable = len(self._cached) - sum(1 for b in hits if self._ref[b] == 0)
        return needed <= self._free_count() + evictable

    # -------------------------------------------------------------- allocation
    def _evict_one(self) -> int:
        blk, _ = self._cached.popitem(last=False)  # LRU
        key = self._key_of.pop(blk)
        # defensive: only drop the registry entry if it still points at this
        # block (acquire deregisters superseded mappings, so a mismatch here
        # would mean a newer block owns the key)
        if self._registry.get(key) == blk:
            # host-tier spill: the victim still owns its key, so its device
            # bytes are the canonical content for that prefix — hand it to
            # the spill hook before the registry entry dies
            if self.spill_fn is not None:
                self.spill_fn(key, blk)
            del self._registry[key]
        return blk

    def _free_count(self) -> int:
        return int(np.count_nonzero(self._is_free))

    def _take_runs(self, at: int, count: int, high: bool) -> List[int]:
        """``count`` free blocks (the free map holds enough) for row entries
        ``at...``, in ascending runs: all of them as one run where the free
        map holds one that long, else a run to the walk's next chunk boundary
        (``run_blocks``), else the longest free run whole. The run is the free
        map's highest that fits, taken from its top, where ``high``; else its
        lowest, taken from its bottom."""
        blocks: List[int] = []
        while len(blocks) < count:
            edges = np.flatnonzero(np.diff(self._is_free, prepend=False, append=False))
            starts, lengths = edges[::2], edges[1::2] - edges[::2]
            rest = count - len(blocks)
            to_boundary = self.run_blocks - (at + len(blocks)) % self.run_blocks
            for want in (rest, min(rest, to_boundary)):
                fits = np.flatnonzero(lengths >= want)
                if fits.size:
                    i = fits[-1] if high else fits[0]
                    break
            else:
                i = int(np.argmax(lengths))
                want = int(lengths[i])
            first = int(starts[i] + lengths[i] - want if high else starts[i])
            self._is_free[first:first + want] = False
            blocks.extend(range(first, first + want))
        return blocks

    def _deregister(self, key: bytes) -> None:
        """Drop ``key``'s registration; a cached block it held is plain free."""
        old = self._registry.pop(key, None)
        if old is not None:
            del self._key_of[old]
            if old in self._cached:  # orphan at ref 0: plain free now
                del self._cached[old]
                self._is_free[old] = True

    def _register(self, key: bytes, blk: int) -> None:
        """Map ``key`` -> ``blk`` in the prefix registry, deregistering any
        superseded mapping first. A stale registration can exist here:
        evicting a shallow prefix block orphans deeper extensions (the
        depth walk stops at the first miss), so this key may still map to
        an old block. Deregister it first — otherwise the old block's
        eventual eviction would delete the NEW registry entry, and evicting
        the new block afterwards would KeyError."""
        if self._registry.get(key) not in (None, blk):
            self._deregister(key)
        self._registry[key] = blk
        self._key_of[blk] = key

    def acquire(self, slot: int, prompt: np.ndarray, budget: int,
                defer_register: bool = False) -> Tuple[np.ndarray, int]:
        """Allocate (or COW-share) the blocks for one admitted request and
        install the slot's table row. Returns ``(row, shared_blocks)`` where
        ``row`` is the full ``(blocks_per_row,)`` int32 table row (null
        beyond the allocation). Raises ``EngineCapacityError`` (a retriable
        RuntimeError) when the pool lacks capacity — callers gate on
        :meth:`can_admit` first.

        ``defer_register=True`` (chunked prefill) parks the fresh prompt
        blocks' registry entries instead of installing them: their content
        does not exist until the slot's chunks commit, so serving prefix
        hits off them would share garbage. :meth:`promote_deferred`
        installs them (host-tier restores make content valid early);
        :meth:`release` before promotion simply drops them — the blocks
        free unregistered, exactly as if they had never been shareable."""
        from .utils.fault import EngineCapacityError

        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        total = self.blocks_needed(len(prompt), budget)
        if total > self.blocks_per_row:
            raise EngineCapacityError(
                f"request needs {total} blocks but a table row holds "
                f"{self.blocks_per_row}"
            )
        if not self.can_admit(prompt, budget):
            raise EngineCapacityError(
                "no free KV blocks (caller must gate on can_admit())"
            )
        bs = self.block_size
        full = len(prompt) // bs
        hits = self._shared_prefix(prompt)
        row: List[int] = []
        for blk in hits:
            if self._ref[blk] == 0:  # cached -> active
                del self._cached[blk]
            self._ref[blk] += 1
            row.append(blk)
        self.prefix_hits += len(hits)
        self.prefix_misses += full - len(hits)
        deferred: List[Tuple[bytes, int]] = []
        if not defer_register:
            # the registrations this request supersedes (deeper blocks of a
            # prefix whose shallower block was evicted) free their cached
            # blocks before any block is chosen, as they would be reused
            for j in range(len(hits), full):
                self._deregister(prompt[: (j + 1) * bs].tobytes())
        # what the free blocks lack is evicted first, LRU first, so the
        # private blocks can be chosen as runs of free ones
        for _ in range(total - len(hits) - self._free_count()):
            self._is_free[self._evict_one()] = True
        # the full prompt blocks past the shared depth register, so that the
        # NEXT request with this prefix shares them: they come from the top of
        # the pool and the rest from the bottom, so that what stays cached
        # after a release lies beside other cached blocks and leaves the rest
        # free in long runs (a chunk of the walk copies two runs whole)
        fresh = max(full - len(hits), 0)
        private = (self._take_runs(len(hits), fresh, high=True)
                   + self._take_runs(len(hits) + fresh, total - len(hits) - fresh, high=False))
        for j, blk in zip(range(len(hits), total), private):
            self._ref[blk] = 1
            if j < full:
                key = prompt[: (j + 1) * bs].tobytes()
                if defer_register:
                    deferred.append((key, blk))
                else:
                    self._register(key, blk)
            row.append(blk)
        if deferred:
            self._deferred[slot] = deferred
        else:
            self._deferred.pop(slot, None)
        self._rows[slot] = row
        self.tables[slot] = _NULL_BLOCK
        self.tables[slot, : len(row)] = row
        return self.tables[slot].copy(), len(hits)

    def promote_deferred(self, slot: int, count: Optional[int] = None) -> int:
        """Install up to ``count`` (all when None) of the slot's parked
        registrations, shallowest-first — called once a chunked prefill's
        content actually exists (host-tier restore made the leading blocks
        valid early; the final chunk's commit validates the rest). Returns
        how many were promoted."""
        deferred = self._deferred.get(slot, [])
        n = len(deferred) if count is None else min(count, len(deferred))
        for key, blk in deferred[:n]:
            self._register(key, blk)
        rest = deferred[n:]
        if rest:
            self._deferred[slot] = rest
        else:
            self._deferred.pop(slot, None)
        return n

    def release(self, slot: int) -> None:
        """Drop the slot's references; zero-ref registered blocks park in
        the cached LRU (still serving prefix hits), unregistered ones free.
        The table row resets to the null block so the ghost slot's masked
        decode writes stop touching real blocks — this is what makes block
        recycling safe under the deferred-readback ring."""
        self._deferred.pop(slot, None)  # cancelled mid-prefill: never shareable
        for blk in self._rows[slot]:
            self._ref[blk] -= 1
            if self._ref[blk] == 0:
                if blk in self._key_of:
                    self._cached[blk] = None  # most-recently-released = MRU
                    self._cached.move_to_end(blk)
                else:
                    self._is_free[blk] = True
        self._rows[slot] = []
        self.tables[slot] = _NULL_BLOCK

    def stats(self) -> dict:
        lookups = self.prefix_hits + self.prefix_misses
        return {
            "blocks_total": self.num_blocks,
            "blocks_free": self._free_count(),
            "blocks_cached": len(self._cached),
            "blocks_active": self.active_blocks(),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_rate": (self.prefix_hits / lookups) if lookups else 0.0,
        }


# ------------------------------------------------------------------- backends
def _recurrent_shape(family, slots: int) -> Optional[tuple]:
    """``(recurrent layers, slots, *state shape)``, or None for a family all of
    whose state is keys and values."""
    if not family.recurrent_layers:
        return None
    return (family.recurrent_layers, slots, *family.recurrent_shape)


def _kv_leaves(family) -> tuple:
    """The cache's leaves of keys and values: a family whose values are a
    prefix of its keys' rows (``value_dim``) keeps the one."""
    return ("k",) if family.value_dim is not None else ("k", "v")


def _write_recurrent(cache, new_cache, slot) -> dict:
    """The ``"recurrent"`` leaf with ``slot``'s row replaced by the prefill's
    (``(layers, 1, *state shape)``); nothing where the family keeps none."""
    if "recurrent" not in cache:
        return {}
    fresh = new_cache["recurrent"][:, 0].astype(cache["recurrent"].dtype)
    return {"recurrent": cache["recurrent"].at[:, slot].set(fresh)}


class KVCacheBackend:
    """Interface both inference paths program against. Device methods
    (``init_device_state``, ``make_layout``, ``prefill_write``) are called
    inside jitted programs; host methods manage admission and the table."""

    kind: str = "abstract"
    # (recurrent layers, slots, *state shape) of a family that keeps such rows
    _recurrent: Optional[tuple] = None

    # device side -----------------------------------------------------------
    def init_device_state(self):
        raise NotImplementedError

    def make_layout(self, tables) -> Optional[PagedKVLayout]:
        """None = the model decode consumes the cache directly (dense)."""
        raise NotImplementedError

    def prefill_write(self, cache, new_cache, slot, table_row, width: int):
        """Scatter a prefill's KV (``(L, 1, max_len, kvh, hd)`` per leaf,
        computed over the first ``width`` positions, the prompt's rung)
        into the store for ``slot``/``table_row``."""
        raise NotImplementedError

    def commit_window(self, cache, window_kv, tables, pos, count):
        """Scatter the first ``count[b]`` columns of a speculative-verify
        window (``window_kv``: ``{"k","v"}`` of ``(L, B, W, kvh, hd)``) into
        the store at positions ``pos .. pos+count-1`` per slot. Columns past
        ``count`` (rejected drafts / padding) are dropped, never clamped
        onto live positions."""
        raise NotImplementedError

    # host side -------------------------------------------------------------
    def device_tables(self):
        raise NotImplementedError

    def acquire(self, slot: int, prompt: np.ndarray, budget: int,
                defer_register: bool = False) -> Tuple[np.ndarray, int]:
        raise NotImplementedError

    def prefill_row(self, row: np.ndarray, shared: int) -> np.ndarray:
        """The table row a prefill writes through, from ``acquire``'s
        ``(row, shared)``."""
        return row

    def release(self, slot: int) -> None:
        raise NotImplementedError

    def can_admit(self, prompt: np.ndarray, budget: int) -> bool:
        raise NotImplementedError

    def validate_request(self, prompt_len: int, budget: int) -> None:
        """Extra structural admission checks (beyond the engine's bucket /
        max_len checks); raises typed ``ValueError``."""

    def reset(self) -> None:
        raise NotImplementedError

    def hbm_bytes(self) -> int:
        raise NotImplementedError

    def reserved_tokens(self) -> int:
        """Positions currently reserved in the store (dense: every slot's
        worst case; paged: allocated blocks × block_size, shared counted
        once)."""
        raise NotImplementedError

    def stats(self) -> dict:
        raise NotImplementedError

    def row_bytes(self) -> int:
        """Bytes one position holds in the store over all its layers, as the
        leaves were allocated (keys and values, or a latent family's one row;
        an int8 pool's scales with them)."""
        raise NotImplementedError

    def recurrent_state_bytes(self) -> int:
        """Bytes of the per-slot recurrent state (0 where the family's only
        state is keys and values); not part of :meth:`hbm_bytes`."""
        if self._recurrent is None:
            return 0
        return int(np.prod(self._recurrent)) * jnp.dtype(self._dtype).itemsize

    def _init_recurrent(self) -> dict:
        if self._recurrent is None:
            return {}
        return {"recurrent": jnp.zeros(self._recurrent, self._dtype)}


class DenseKVBackend(KVCacheBackend):
    """Today's arena semantics behind the backend interface: one dense
    ``(L, slots, max_len, kvh, hd)`` row per slot, full-row prefill wipe
    (structural KV isolation), no admission constraint beyond slots."""

    kind = "dense"

    def __init__(self, *, config, slots: int, max_len: int):
        self.config = config
        self.slots = slots
        self.max_len = max_len
        family = config.serving_family()
        self._shape = (family.kv_layers, slots, max_len, family.kv_heads, family.head_dim)
        self._leaves = _kv_leaves(family)
        self._recurrent = _recurrent_shape(family, slots)
        self._dtype = config.compute_dtype
        # tables are inert for dense; a constant (slots, 1) zero array keeps
        # the engine's program signatures uniform across backends
        self._tables = jnp.zeros((slots, 1), jnp.int32)

    def init_device_state(self):
        return {
            **{which: jnp.zeros(self._shape, self._dtype) for which in self._leaves},
            **self._init_recurrent(),
        }

    def make_layout(self, tables):
        return None

    @jax.named_scope("kv.scatter")
    def prefill_write(self, cache, new_cache, slot, table_row, width):
        # full-row dynamic_update_slice whatever the rung: zeros beyond it
        # wipe every stale byte of the slot's previous occupant
        return {
            **{
                which: lax.dynamic_update_slice(
                    cache[which],
                    new_cache[which].astype(cache[which].dtype),
                    (0, slot, 0, 0, 0),
                )
                for which in self._leaves
            },
            **_write_recurrent(cache, new_cache, slot),
        }

    @jax.named_scope("kv.scatter")
    def commit_window(self, cache, window_kv, tables, pos, count):
        w = window_kv["k"].shape[2]
        j = jnp.arange(w, dtype=jnp.int32)[None, :]
        idx = pos[:, None] + j  # (S, W) absolute positions
        valid = (j < count[:, None]) & (idx < self.max_len)
        idx = jnp.where(valid, idx, self.max_len)  # pushed OOB -> dropped
        rows = jnp.arange(self.slots)[:, None]
        return {
            **cache,
            **{
                which: cache[which].at[:, rows, idx].set(
                    window_kv[which].astype(cache[which].dtype), mode="drop"
                )
                for which in self._leaves
            },
        }

    def device_tables(self):
        return self._tables

    def acquire(self, slot, prompt, budget, defer_register: bool = False):
        return np.zeros((1,), np.int32), 0

    def release(self, slot):
        pass

    def can_admit(self, prompt, budget):
        return True

    def reset(self):
        pass

    def hbm_bytes(self):
        return len(self._leaves) * int(np.prod(self._shape)) * jnp.dtype(self._dtype).itemsize

    def row_bytes(self):
        return self.hbm_bytes() // (self.slots * self.max_len)

    def reserved_tokens(self):
        return self.slots * self.max_len

    def stats(self):
        return {
            "backend": self.kind,
            # dense decode reads the whole arena every step: live == pool
            "hbm_bytes": self.hbm_bytes(),
            "hbm_bytes_live": self.hbm_bytes(),
            "reserved_tokens": self.reserved_tokens(),
            "recurrent_state_bytes": self.recurrent_state_bytes(),
        }


class PagedKVBackend(KVCacheBackend):
    """Block pool + tables + COW prefix cache (+ optional int8 storage).

    ``pool_blocks=None`` fully provisions: ``slots * max_len/block_size``
    blocks + the null block — same token capacity as the dense arena.
    Smaller pools oversubscribe: more slots than worst-case HBM, with
    admission gated on actual free blocks (the whole point)."""

    def __init__(self, *, config, slots: int, max_len: int, prompt_bucket: int,
                 block_size: int = 16, pool_blocks: Optional[int] = None,
                 quantized: bool = False, attention_impl: str = "reference",
                 host_tier_bytes: int = 0):
        if attention_impl not in ("reference", "pallas"):
            raise ValueError(
                f"attention_impl must be 'reference' or 'pallas', "
                f"got {attention_impl!r}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_len % block_size != 0:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of engine_block_size "
                f"({block_size}) so a table row covers it exactly"
            )
        self.config = config
        self.slots = slots
        self.max_len = max_len
        self.block_size = block_size
        self.blocks_per_row = max_len // block_size
        self.prefill_blocks = _ceil_div(prompt_bucket, block_size)
        self.quantized = quantized
        if pool_blocks is None:
            pool_blocks = slots * self.blocks_per_row + 1
        if pool_blocks < self.prefill_blocks + 1:
            raise ValueError(
                f"engine_pool_blocks ({pool_blocks}) must cover at least one "
                f"bucketed prefill + the null block "
                f"({self.prefill_blocks + 1} blocks of engine_block_size="
                f"{block_size})"
            )
        self.pool_blocks = pool_blocks
        family = config.serving_family()
        self._kvh, self._hd = family.kv_heads, family.head_dim
        self._leaves = _kv_leaves(family)
        if family.value_dim is not None and (quantized or host_tier_bytes > 0):
            raise ValueError(
                f"{'kv_cache=paged_int8' if quantized else 'kv_host_tier_bytes'} cannot "
                "serve a latent cache (a family whose values are the first columns of "
                "its keys' rows, one leaf): the int8 pool keeps one scale a position "
                "over keys and another over values, and the host tier spills and "
                "restores a block as a pair of them (the missing piece: a scale for "
                "each part of a latent row, and one-leaf spill payloads)"
            )
        # the pool's leading axis: the layers that keep keys and values
        self._layers = family.kv_layers
        self._recurrent = _recurrent_shape(family, slots)
        self._dtype = config.compute_dtype
        self.kind = "paged_int8" if quantized else "paged"
        self.attention_impl = attention_impl
        from .ops.paged_decode import decode_chunk_positions

        self.pool = PagedBlockPool(
            num_blocks=pool_blocks, block_size=block_size, slots=slots,
            blocks_per_row=self.blocks_per_row,
            # a slot's runs break where the decode walk's chunks do
            run_blocks=decode_chunk_positions(block_size, family.value_dim is not None)
            // block_size,
        )
        self._device_tables_cache = None
        # ---------------------------------------------- host-RAM spill tier
        # Evicted registered blocks spill to pinned host RAM instead of
        # dying (docs/serving.md "Long-context serving"). The hot path only
        # dispatches a device-side gather (read-only on the pool — a crash
        # anywhere after that point cannot corrupt device state); a
        # background thread materializes the gather to host numpy and
        # inserts it into the tier.
        self.host_tier: Optional[HostKVTier] = None
        self._cache_reader: Optional[Callable[[], Any]] = None
        self._spill_batch: List[Tuple[bytes, int]] = []
        self._spill_q: Optional["queue.Queue"] = None
        self._spill_thread: Optional[threading.Thread] = None
        # admission-time async prefetch: key -> device payload already in
        # flight via jax.device_put, consumed (or discarded) at restore
        self._prefetched: Dict[bytes, Any] = {}
        self.prefetch_hits = 0
        if host_tier_bytes > 0:
            if self._recurrent is not None:
                raise ValueError(
                    "kv_host_tier_bytes cannot serve a family with recurrent "
                    "state: a restored prefix skips the prompt forward that "
                    "state comes from, and the host tier keeps no snapshot of "
                    "it per prefix (the missing piece: recurrent-state "
                    "snapshots keyed like the blocks)"
                )
            self.host_tier = HostKVTier(
                host_tier_bytes, self.host_block_bytes()
            )
            self.pool.spill_fn = (
                lambda key, blk: self._spill_batch.append((key, blk))
            )

    # ------------------------------------------------------------ device side
    def init_device_state(self):
        shape = (self._layers, self.pool_blocks, self.block_size, self._kvh * self._hd)
        if self.quantized:
            leaf = lambda: {
                "q": jnp.zeros(shape, jnp.int8),
                "s": jnp.zeros(shape[:3], jnp.float32),
            }
            return {"k": leaf(), "v": leaf(), **self._init_recurrent()}
        return {**{which: jnp.zeros(shape, self._dtype) for which in self._leaves},
                **self._init_recurrent()}

    def make_layout(self, tables):
        return PagedKVLayout(
            tables, self.block_size, self._dtype, self._hd,
            attention_impl=self.attention_impl,
        )

    @jax.named_scope("kv.scatter")
    def prefill_write(self, cache, new_cache, slot, table_row, width):
        """One scatter of a prefill's KV into the slot's blocks, every layer
        at once (``[:, ids]``, as a restore writes them). The block count is
        the rung's, ``ceil(width / block_size)`` (static: one compiled
        program a rung), so a prefill at half the bucket writes half the
        blocks and leaves the row's later blocks as they were; rows whose
        allocation is shorter than the rung carry null-block table entries
        there, harmlessly absorbing the extra writes, and so do shared (COW)
        prefix blocks (:meth:`prefill_row`)."""
        n, bs = _ceil_div(width, self.block_size), self.block_size
        ids = table_row[:n]
        out = _write_recurrent(cache, new_cache, slot)
        for which in self._leaves:
            pool = cache[which]
            fresh = new_cache[which][:, 0, : n * bs]  # (L, n * bs, kvh, hd)
            fresh = fresh.reshape(fresh.shape[0], n, bs, *fresh.shape[2:])
            if self.quantized:
                q, s = kv_quantize(fresh)
                out[which] = {
                    "q": pool["q"].at[:, ids].set(_merge_heads(q)),
                    "s": pool["s"].at[:, ids].set(s),
                }
            else:
                out[which] = pool.at[:, ids].set(
                    _merge_heads(fresh).astype(pool.dtype)
                )
        return out

    def commit_window(self, cache, window_kv, tables, pos, count):
        layout = self.make_layout(tables)
        return {
            **cache,
            **{
                which: layout.commit_window(cache[which], window_kv[which], pos, count)
                for which in self._leaves
            },
        }

    # -------------------------------------------------------------- host side
    def device_tables(self):
        if self._device_tables_cache is None:
            # a copy of its own: on the CPU client `jnp.asarray` takes a
            # 64-byte-aligned numpy array without copying it, and a step
            # still in flight would then read the rows that the next
            # `acquire` or `release` writes (a vacant slot scattering its
            # keys through a row installed after the step was dispatched)
            self._device_tables_cache = jnp.asarray(self.pool.tables.copy())
        return self._device_tables_cache

    def acquire(self, slot, prompt, budget, defer_register: bool = False):
        row, shared = self.pool.acquire(
            slot, prompt, budget, defer_register=defer_register
        )
        self._device_tables_cache = None
        self._flush_spills()
        return row, shared

    def prefill_row(self, row, shared):
        """``row`` with its ``shared`` leading (COW prefix) blocks sent to the
        null block: their content is what the prefill that registered them
        wrote, and a sharer's prefill, which may run at another rung and so
        round otherwise, never writes it again."""
        row = row.copy()
        row[:shared] = _NULL_BLOCK
        return row

    def prefix_digest(self, limit: int = 512) -> List[int]:
        """Compact fingerprint of this backend's prefix registry: crc32 of
        each registered block-aligned prefix key, capped at ``limit``. The
        fleet gossips these via probe snapshots so the router can score
        KV-affinity (a replica already holding a request's prefix skips the
        prefill work entirely). Collisions only cost a mis-scored bonus —
        correctness never depends on the digest."""
        # list() copy: the registry dict mutates on the serving thread while
        # the prober reads it here; crc over a snapshot is race-free.
        keys = list(self.pool._registry.keys())[: max(0, limit)]
        return [zlib.crc32(k) & 0xFFFFFFFF for k in keys]

    # ---------------------------------------------------- host tier: spill
    def host_block_bytes(self) -> int:
        """Host bytes one spilled block occupies (K + V payload; int8 keeps
        the quantized bytes + f32 scales — a spilled block restores to the
        identical pool bytes it held)."""
        return len(self._leaves) * self._per_block_bytes()

    def bind_cache_reader(self, reader: Callable[[], Any]) -> None:
        """The engine hands us a zero-cost view of its CURRENT donated
        device cache — the spill gather reads through this right after
        ``pool.acquire`` evicted a victim and BEFORE the caller dispatches
        the program that overwrites the block."""
        self._cache_reader = reader

    def _flush_spills(self) -> None:
        """Snapshot this acquire's eviction victims with ONE device-side
        gather (read-only on the pool) and queue the host materialization
        on the background spill thread. Called while still inside the
        admission path — the overwriting prefill has not dispatched yet, so
        the gathered bytes are the victims' canonical content."""
        batch, self._spill_batch = self._spill_batch, []
        if not batch or self.host_tier is None or self._cache_reader is None:
            return
        cache = self._cache_reader()
        if cache is None:
            return
        keys = [key for key, _ in batch]
        ids = jnp.asarray([blk for _, blk in batch], jnp.int32)
        if self.quantized:
            payload = {
                w: {"q": cache[w]["q"][:, ids], "s": cache[w]["s"][:, ids]}
                for w in ("k", "v")
            }
        else:
            payload = {w: cache[w][:, ids] for w in ("k", "v")}
        self._spill_worker_q().put((keys, payload))

    def _spill_worker_q(self) -> "queue.Queue":
        if self._spill_q is None:
            self._spill_q = queue.Queue()
            self._spill_thread = threading.Thread(
                target=self._spill_worker, name="kv-spill", daemon=True
            )
            self._spill_thread.start()
        return self._spill_q

    def _spill_worker(self) -> None:
        from .utils.fault import fault_point

        while True:
            item = self._spill_q.get()
            try:
                if item is None:
                    return
                keys, payload = item
                # kill point: dying here (mid device_get, tier half-written)
                # must never corrupt the device pool — the gather upstream
                # was read-only and the tier is host-only state
                fault_point("kvcache.spill_mid")
                host = jax.tree_util.tree_map(np.asarray, payload)
                for i, key in enumerate(keys):
                    if self.quantized:
                        block = {
                            w: {"q": host[w]["q"][:, i], "s": host[w]["s"][:, i]}
                            for w in ("k", "v")
                        }
                    else:
                        block = {w: host[w][:, i] for w in ("k", "v")}
                    self.host_tier.insert(key, block)
            except Exception:  # noqa: BLE001 — a failed spill only loses a cache win
                logger.exception(
                    "host-tier spill failed; the evicted block is lost to "
                    "the tier (device pool unaffected)"
                )
            finally:
                self._spill_q.task_done()

    def spill_flush(self, timeout_s: float = 30.0) -> None:
        """Block (bounded) until every queued spill has landed in the tier
        (tests/benches; the serving hot path never calls this)."""
        if self._spill_q is None:
            return
        deadline = time.monotonic() + timeout_s
        while self._spill_q.unfinished_tasks:  # graft: race-ok — monotone counter, polled
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"host-tier spill queue did not drain in {timeout_s}s "
                    f"({self._spill_q.unfinished_tasks} task(s) pending)"
                )
            time.sleep(0.002)

    # -------------------------------------------------- host tier: restore
    def _host_chain(self, prompt: np.ndarray, start_depth: int) -> List[bytes]:
        """Consecutive host-tier hits for ``prompt`` starting at block depth
        ``start_depth`` (first miss stops the walk, mirroring the device
        registry's depth walk)."""
        if self.host_tier is None:
            return []
        bs = self.block_size
        keys: List[bytes] = []
        for depth in range(start_depth, len(prompt) // bs):
            key = prompt[: (depth + 1) * bs].tobytes()
            if key in self._prefetched:
                keys.append(key)
                continue
            if self.host_tier.lookup(key) is None:
                break
            keys.append(key)
        return keys

    def prefetch(self, prompt) -> int:
        """Admission-time async prefetch: start ``jax.device_put`` for every
        host-tier block this prompt would restore, so the transfer overlaps
        queue wait instead of sitting on the admission path. Returns how
        many blocks are now in flight."""
        if self.host_tier is None:
            return 0
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        shared = len(self.pool._shared_prefix(prompt))
        n = 0
        for key in self._host_chain(prompt, shared):
            if key not in self._prefetched:
                payload = self.host_tier.lookup(key)
                if payload is None:
                    break
                self._prefetched[key] = jax.device_put(payload)
            n += 1
        return n

    def restore_plan(self, slot: int, prompt: np.ndarray, shared: int,
                     row: np.ndarray):
        """Build the spill-tier restore plan for a chunked admission:
        device payloads (prefetched when possible, ``device_put`` now
        otherwise) for the consecutive host-tier hits past the device
        registry's ``shared`` depth, targeted at the slot's freshly
        allocated blocks ``row[shared : shared+n]``. Returns ``(n_blocks,
        payloads, target_ids)`` or ``None`` on a cold tier. The caller
        scatters the payloads with its restore program, then promotes the
        slot's first ``n_blocks`` deferred registrations — restored content
        is valid (it IS the original bytes), so it may serve prefix hits
        immediately."""
        if self.host_tier is None:
            return None
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        keys = self._host_chain(prompt, shared)
        payloads = []
        for key in keys:
            dev = self._prefetched.pop(key, None)
            if dev is None:
                host = self.host_tier.lookup(key)
                if host is None:  # raced out of the tier since the probe
                    break
                dev = jax.device_put(host)
            else:
                self.prefetch_hits += 1
            payloads.append(dev)
        if not payloads:
            return None
        n = len(payloads)
        self.host_tier.count_restore(n)
        target_ids = np.asarray(row[shared: shared + n], np.int32)
        return n, payloads, target_ids

    def release(self, slot):
        self.pool.release(slot)
        self._device_tables_cache = None

    def can_admit(self, prompt, budget):
        return self.pool.can_admit(prompt, budget)

    def validate_request(self, prompt_len, budget):
        needed = self.pool.blocks_needed(prompt_len, budget)
        if needed > min(self.pool.max_request_blocks(), self.blocks_per_row):
            raise ValueError(
                f"request needs {needed} KV blocks "
                f"(engine_block_size={self.block_size}) but the pool only "
                f"has {min(self.pool.max_request_blocks(), self.blocks_per_row)} "
                "allocatable blocks per request; raise "
                "ServingConfig.engine_pool_blocks / engine_max_len or lower "
                "the budget"
            )

    def promote_deferred(self, slot: int, count: Optional[int] = None) -> int:
        return self.pool.promote_deferred(slot, count)

    def reset(self):
        self.pool.reset()
        self._device_tables_cache = None
        # the host tier SURVIVES a device reset: its keys are content-
        # addressed (exact prefix bytes + deterministic quantization), so
        # recovered engines restore instead of recomputing warm prefixes.
        # In-flight prefetches are dropped (their device buffers die with
        # the arena they were destined for).
        self._spill_batch = []
        self._prefetched = {}

    def _per_block_bytes(self):
        per_block = self._layers * self.block_size * self._kvh * self._hd
        if self.quantized:
            # int8 payload + f32 per-position scales
            per_block = per_block * 1 + self._layers * self.block_size * 4
        else:
            per_block *= jnp.dtype(self._dtype).itemsize
        return per_block

    def hbm_bytes(self):
        return len(self._leaves) * self.pool_blocks * self._per_block_bytes()

    def row_bytes(self):
        return len(self._leaves) * self._per_block_bytes() // self.block_size

    def hbm_bytes_live(self):
        """Bytes the Pallas flash-decode kernel actually reads per step:
        allocated (refcounted) blocks only — the dead tail of each table
        row is compute-skipped and the null block is never live. The pool
        footprint (:meth:`hbm_bytes`) stays what HBM *holds*; this is what
        a decode step *touches* — the runtime counterpart of the G203
        per-program HBM table's pallas rows."""
        return len(self._leaves) * self.pool.active_blocks() * self._per_block_bytes()

    def reserved_tokens(self):
        return (self.pool.active_blocks()) * self.block_size

    def stats(self):
        out = {
            "backend": self.kind,
            "block_size": self.block_size,
            "pool_blocks": self.pool_blocks,
            "attention_impl": self.attention_impl,
            "hbm_bytes": self.hbm_bytes(),
            "hbm_bytes_live": self.hbm_bytes_live(),
            "reserved_tokens": self.reserved_tokens(),
            "recurrent_state_bytes": self.recurrent_state_bytes(),
            **self.pool.stats(),
        }
        if self.host_tier is not None:
            out.update(self.host_tier.stats())
            out["prefetch_hits"] = self.prefetch_hits
        return out


def make_kv_backend(kind: str, *, config, slots: int, max_len: int,
                    prompt_bucket: int, block_size: int = 16,
                    pool_blocks: Optional[int] = None,
                    attention_impl: str = "reference",
                    host_tier_bytes: int = 0) -> KVCacheBackend:
    """Factory the engine (and ``ServingConfig.kv_cache``) selects through."""
    if kind == "dense":
        if attention_impl != "reference":
            raise ValueError(
                "attention_impl='pallas' requires a paged KV cache "
                "(kv_cache='paged' or 'paged_int8'); the dense arena has no "
                "block tables for the kernel to walk"
            )
        if host_tier_bytes > 0:
            raise ValueError(
                "kv_host_tier_bytes requires a paged KV cache (kv_cache="
                "'paged' or 'paged_int8'); the dense arena has no blocks "
                "to spill"
            )
        return DenseKVBackend(config=config, slots=slots, max_len=max_len)
    if kind in ("paged", "paged_int8"):
        return PagedKVBackend(
            config=config, slots=slots, max_len=max_len,
            prompt_bucket=prompt_bucket, block_size=block_size,
            pool_blocks=pool_blocks, quantized=(kind == "paged_int8"),
            attention_impl=attention_impl, host_tier_bytes=host_tier_bytes,
        )
    raise ValueError(
        f"kv_cache must be one of {KV_BACKENDS}, got {kind!r}"
    )


# --------------------------------------------------- static generate() bridge
def pool_from_dense(cache, block_size: int, quantized: bool):
    """Re-lay a dense prefill cache ``(L, B, total_len, kvh, hd)`` as a
    block pool with identity tables — the bridge that lets static
    ``generate()`` run its decode scan through the same
    :class:`PagedKVLayout` gather/commit ops as the engine (one KV story,
    bitwise parity in f32). ``total_len`` must divide by ``block_size``. A leaf
    that is not keys or values (a family's recurrent state) passes through; a
    latent family's cache has no ``"v"`` to relay."""
    def relay(dense):
        L, b, total, kvh, hd = dense.shape
        nb = total // block_size
        pool = dense.reshape(L, b * nb, block_size, kvh, hd)
        if quantized:
            q, s = kv_quantize(pool)
            return {"q": _merge_heads(q), "s": s}
        return _merge_heads(pool)
    relaid = {which: relay(cache[which]) for which in ("k", "v") if which in cache}
    b = cache["k"].shape[1]
    nb = cache["k"].shape[2] // block_size
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    return {**cache, **relaid}, tables
