"""Sharded, deterministic data pipeline.

TPU-native re-design of the reference's ``data_loader.py`` (1,473 LoC,
/root/reference/src/accelerate/data_loader.py). Same user-facing vocabulary —
``prepare_data_loader``, ``BatchSamplerShard``, ``IterableDatasetShard``,
``SeedableRandomSampler``, ``DataLoaderShard``, ``DataLoaderDispatcher``,
``skip_first_batches`` — but the execution model is single-controller SPMD:

* every step produces ONE global batch as a pytree of ``jax.Array``s sharded
  over the mesh's data axes (``dp_replicate × dp_shard``); TP/PP ranks never
  see "their own" batch because there is no per-rank batch — replication
  across non-data axes is part of the array's sharding, which subsumes the
  reference's mesh-aware rank bookkeeping (data_loader.py:1129-1165);
* on multi-host, each process loads only the rows its local devices own
  (derived from the sharding's index map — the analogue of
  ``BatchSamplerShard``'s stride math) and the global array is assembled with
  ``jax.make_array_from_process_local_data``;
* host→HBM transfer is overlapped with compute by a background prefetch
  thread (the role of ``MpDeviceLoaderWrapper``, data_loader.py:670-721).
"""

from __future__ import annotations

import collections
import copy
import math
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import tracing
from .logging import get_logger
from .state import GradientState, PartialState
from .utils.random import synchronize_rng_states

logger = get_logger(__name__)

__all__ = [
    "SeedableRandomSampler",
    "BatchSamplerShard",
    "IterableDatasetShard",
    "DataLoaderShard",
    "DataLoaderDispatcher",
    "prepare_data_loader",
    "skip_first_batches",
    "default_collate",
    "make_padded_collate",
]


# --------------------------------------------------------------------- helpers
def default_collate(samples: Sequence[Any]):
    """Stack a list of samples (pytrees of arrays / scalars) into a batch."""
    first = samples[0]
    if isinstance(first, dict):
        return type(first)({k: default_collate([s[k] for s in samples]) for k in first})
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate([s[i] for s in samples]) for i in range(len(first)))
    arrs = [np.asarray(s) for s in samples]
    return np.stack(arrs, axis=0)


def make_padded_collate(
    pad_token_id: int = 0,
    max_length: Optional[int] = None,
    ragged_keys: Sequence[str] = ("input_ids",),
    emit_loss_mask: bool = True,
):
    """Collate_fn for VARIABLE-LENGTH samples: ragged keys are padded to the
    batch max (or ``max_length``) via the threaded C++ kernel
    (csrc/packing.cpp collate_padded; NumPy fallback) and a matching
    ``loss_mask`` is emitted so padding never contributes loss. Non-ragged
    keys go through :func:`default_collate`. XLA note: pass ``max_length``
    for a fixed shape — batch-max padding recompiles per distinct length."""
    from .utils.native import collate_padded

    def collate(samples: Sequence[Any]):
        if not samples:
            return {}
        if not isinstance(samples[0], dict):
            tokens, mask = collate_padded(samples, max_length, pad_token_id)
            out = {"input_ids": tokens}
            if emit_loss_mask:
                out["loss_mask"] = mask
            return out
        # one COMMON width for every ragged key (their shapes must line up —
        # e.g. labels vs the logits derived from input_ids), and the mask
        # always describes the PRIMARY ragged key (ragged_keys[0])
        present = [k for k in ragged_keys if k in samples[0]]
        width = max_length
        if width is None and present:
            width = max(
                len(np.asarray(s[k]).ravel()) for s in samples for k in present
            )
        out = {}
        mask = None
        for key in samples[0]:
            values = [s[key] for s in samples]
            if key in present:
                out[key], key_mask = collate_padded(values, width, pad_token_id)
                if key == present[0]:
                    mask = key_mask
            else:
                out[key] = default_collate(values)
        if emit_loss_mask and mask is not None and "loss_mask" not in out:
            out["loss_mask"] = mask
        return out

    return collate


def batch_sharding(
    mesh: Mesh,
    batch_axes: Sequence[str] = ("dp_replicate", "dp_shard"),
    seq_axes: Sequence[str] = (),
) -> NamedSharding:
    """Sharding for a batch pytree: dim 0 over the data axes; when CP/SP is
    active, dim 1 (sequence) over the seq axes. Rank-1 leaves only get the
    batch axes (see ``_BaseAcceleratedLoader._place``)."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    s_axes = tuple(a for a in seq_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    if not axes and not s_axes:
        return NamedSharding(mesh, P())
    if s_axes:
        return NamedSharding(mesh, P(axes if axes else None, s_axes))
    return NamedSharding(mesh, P(axes))


def _is_torch_loader(obj) -> bool:
    try:
        import torch.utils.data as tud

        return isinstance(obj, tud.DataLoader)
    except ImportError:
        return False


def data_shard_info(
    sharding: NamedSharding,
    process_index: Optional[int] = None,
    num_processes: Optional[int] = None,
    process_of_device: Optional[Callable] = None,
) -> tuple[int, int, int]:
    """Mesh-aware data-shard math: which slice of the batch dim must THIS
    process read, given that non-data axes (tp/cp/sp/pp) may span processes
    that therefore need IDENTICAL rows (reference data_loader.py:1129-1165
    derives effective process_index/num_processes from the device mesh).

    Returns (num_shards, shard_index, rows_per_shard_factor) where the
    dataset is read in ``num_shards`` distinct slices and this process reads
    slice ``shard_index``; each slice covers ``rows_per_shard_factor`` of the
    per-process batch rows (== local dp rows).
    """
    state = PartialState()
    process_index = state.process_index if process_index is None else process_index
    num_processes = state.num_processes if num_processes is None else num_processes
    if process_of_device is None:
        process_of_device = lambda d: d.process_index
    mesh = sharding.mesh
    spec0 = sharding.spec[0] if len(sharding.spec) else None
    axes = () if spec0 is None else ((spec0,) if isinstance(spec0, str) else tuple(spec0))
    n_rows = 1
    for a in axes:
        n_rows *= mesh.shape[a]
    if n_rows <= 1 or num_processes <= 1:
        return 1, 0, 1
    # map each dim-0 row block to the set of processes whose devices own it
    idx_map = sharding.devices_indices_map((n_rows,))
    proc_rows: dict[int, set] = {}
    for dev, slices in idx_map.items():
        sl = slices[0]
        start = sl.start or 0
        stop = sl.stop if sl.stop is not None else n_rows
        proc_rows.setdefault(process_of_device(dev), set()).update(range(start, stop))
    # group processes by identical row sets → distinct data shards
    groups: dict[frozenset, list[int]] = {}
    for proc, rows in proc_rows.items():
        groups.setdefault(frozenset(rows), []).append(proc)
    ordered = sorted(groups.items(), key=lambda kv: min(kv[0]))
    num_shards = len(ordered)
    shard_index = 0
    for i, (rows, procs) in enumerate(ordered):
        if process_index in procs:
            shard_index = i
            break
    rows_per_shard = n_rows // num_shards
    return num_shards, shard_index, rows_per_shard


# --------------------------------------------------------------------- sampler
class SeedableRandomSampler:
    """Deterministic shuffling sampler: reseeds with ``seed + epoch`` each
    epoch so resumed runs see identical order (reference data_loader.py:73-107)."""

    def __init__(self, data_source_len: int, seed: int = 0, epoch: int = 0, generator=None):
        self.data_source_len = data_source_len
        self.seed = seed
        self.epoch = epoch

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return self.data_source_len

    def __iter__(self) -> Iterator[int]:
        rng = np.random.default_rng(self.seed + self.epoch)
        yield from rng.permutation(self.data_source_len).tolist()


class BatchSamplerShard:
    """Shard a batch sampler across ``num_processes`` so each yields its own
    sub-batches (reference data_loader.py:110-271).

    Two modes, mirroring the reference:
      * ``split_batches=False`` (default): the underlying sampler yields
        batches of per-process size; process ``i`` takes batch ``k`` where
        ``k % num_processes == i`` (stride mode);
      * ``split_batches=True``: the sampler yields global-size batches and
        each process slices its ``1/num_processes`` chunk.

    ``even_batches=True`` loops back to the start so every process yields the
    same number of equally-sized batches (required for fixed-shape XLA).
    """

    def __init__(
        self,
        batch_sampler: Iterable[list[int]],
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        import collections.abc

        if (
            split_batches
            and num_processes > 1
            # probing a one-shot iterator would consume its first batch
            and not isinstance(batch_sampler, collections.abc.Iterator)
        ):
            first = next(iter(batch_sampler), None)
            if first is not None and len(first) % num_processes != 0:
                raise ValueError(
                    f"split_batches=True requires batch size ({len(first)}) divisible "
                    f"by num_processes ({num_processes})"
                )
        self.batch_sampler = batch_sampler
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    @property
    def total_length(self) -> int:
        return len(self.batch_sampler)

    def __len__(self) -> int:
        n = len(self.batch_sampler)
        if self.split_batches:
            return n
        if n % self.num_processes == 0:
            return n // self.num_processes
        length = n // self.num_processes
        if self.drop_last:
            return length
        return length + 1 if self.even_batches else length + int(
            self.process_index < n % self.num_processes
        )

    def __iter__(self) -> Iterator[list[int]]:
        if self.split_batches:
            yield from self._iter_split()
        else:
            yield from self._iter_stride()

    def _iter_split(self):
        for batch in self.batch_sampler:
            size = len(batch) // self.num_processes
            start = self.process_index * size
            chunk = batch[start : start + size]
            if len(chunk) == size or not self.drop_last:
                if len(chunk) < size and self.even_batches and len(batch) > 0:
                    chunk = chunk + batch[: size - len(chunk)]
                if chunk:
                    yield chunk
    def _iter_stride(self):
        import itertools

        it = iter(self.batch_sampler)
        stored: list[list[int]] = []  # first full cycle, kept for tail refill
        while True:
            cycle = list(itertools.islice(it, self.num_processes))
            if not cycle:
                return
            size = self.batch_size or len(cycle[0])
            complete = len(cycle) == self.num_processes and len(cycle[-1]) == size
            if complete:
                if len(stored) < self.num_processes:
                    stored.extend(cycle)
                yield cycle[self.process_index]
                continue
            # Incomplete final cycle (short last batch and/or fewer batches
            # than processes): loop data from the start so every process gets
            # an equal number of full-size batches (reference :110-271).
            if self.drop_last:
                return
            if not self.even_batches:
                if self.process_index < len(cycle):
                    yield cycle[self.process_index]
                return
            pool = [i for b in (stored or cycle) for i in b]
            batch = cycle[self.process_index] if self.process_index < len(cycle) else []
            fill = 0
            while len(batch) < size and pool:
                batch = batch + [pool[fill % len(pool)]]
                fill += 1
            if batch:
                yield batch
            return


class IterableDatasetShard:
    """Shard an iterable dataset: buffer ``batch_size * num_processes``
    samples, each process takes its slice (reference data_loader.py:274-370)."""

    def __init__(
        self,
        dataset: Iterable,
        batch_size: int = 1,
        drop_last: bool = False,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        seed: int = 0,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.seed = seed
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __iter__(self):
        real_batch_size = (
            self.batch_size if self.split_batches else self.batch_size * self.num_processes
        )
        process_slice = range(
            self.process_index * (real_batch_size // self.num_processes),
            (self.process_index + 1) * (real_batch_size // self.num_processes),
        )
        first_batch = None
        current_batch = []
        for element in self.dataset:
            current_batch.append(element)
            if len(current_batch) == real_batch_size:
                for i in process_slice:
                    yield current_batch[i]
                if first_batch is None:
                    first_batch = current_batch.copy()
                current_batch = []
        if not self.drop_last and len(current_batch) > 0:
            if first_batch is None:
                first_batch = current_batch.copy()
            while len(current_batch) < real_batch_size:
                current_batch += first_batch
            for i in process_slice:
                yield current_batch[i]


# ------------------------------------------------------------------- prefetch
class _DevicePrefetcher:
    """Background thread staging host batches onto the mesh while the previous
    step computes — the ``MpDeviceLoaderWrapper`` role (data_loader.py:670-721).
    Depth 2 double-buffers without pinning excess HBM.

    A consumer that abandons iteration early (break / exception) must call
    :meth:`close`: without it the daemon worker stays blocked in ``q.put``
    forever, holding already-staged device batches pinned in HBM (and the
    underlying host iterator open). The owning loader's iterator cleanup and
    re-iteration both call it."""

    _SENTINEL = object()

    def __init__(self, iterator: Iterator, put_fn: Callable[[Any], Any], depth: int = 2):
        self.iterator = iterator
        self.put_fn = put_fn
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._fetches = 0
        self.thread = threading.Thread(target=self._worker, daemon=True)
        self.thread.start()

    def _put(self, item) -> bool:
        """Bounded put that yields to a close() signal instead of blocking
        forever on a full queue with no consumer. Returns False on stop."""
        while not self._stop.is_set():
            try:
                self.q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for item in self.iterator:
                if self._stop.is_set():
                    return
                if not self._put(self.put_fn(item)):
                    return
        except BaseException as e:  # noqa: BLE001 - reraised on main thread
            self.error = e
        finally:
            self._put(self._SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        # the blocking get IS the data wait: span duration shows how long
        # the step loop stalled on input (one span every fetch)
        step = self._fetches
        self._fetches += 1
        with tracing.span("train.data_wait", step=step):
            item = self.q.get()
        if item is self._SENTINEL:
            if self.error is not None:
                raise self.error
            raise StopIteration
        return item

    @property
    def closed(self) -> bool:
        return self._stop.is_set() and not self.thread.is_alive()

    def close(self, timeout: float = 5.0) -> bool:
        """Signal the worker, drain staged batches (releasing their HBM),
        and join. Idempotent; safe from any thread. Returns True when the
        worker exited within ``timeout``."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self.thread.is_alive() and time.monotonic() < deadline:
            # drain so a put-blocked worker can observe the stop flag
            try:
                while True:
                    self.q.get_nowait()
            except queue.Empty:
                pass
            self.thread.join(timeout=0.05)
        # final drain: nothing staged may stay pinned behind the queue
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        return not self.thread.is_alive()


# ------------------------------------------------------------------- loaders
class _BaseAcceleratedLoader:
    """Shared machinery: GradientState registration, one-batch lookahead to
    flag ``end_of_dataloader`` (reference data_loader.py:584-608), remainder
    tracking for ``gather_for_metrics`` duplicate-dropping."""

    def __init__(
        self,
        sharding: Optional[NamedSharding],
        device_prefetch: bool = True,
        rng_types: Optional[Sequence[str]] = None,
        synchronized_generator=None,
        total_dataset_length: Optional[int] = None,
        total_batch_size: Optional[int] = None,
    ):
        self.sharding = sharding
        self.device_prefetch = device_prefetch
        self.rng_types = rng_types
        self.synchronized_generator = synchronized_generator
        self.gradient_state = GradientState()
        self.end_of_dataloader = False
        self.remainder = -1
        self.total_dataset_length = total_dataset_length
        self._total_batch_size = total_batch_size
        self.iteration = 0
        # exact mid-epoch position: batches handed to the training loop this
        # epoch (skipped batches count). The sampler.bin role — reference
        # checkpointing.py:154-179 + torchdata StatefulDataLoader backing.
        self._position = 0
        self._skip_once = 0  # one-shot resume skip set by load_state_dict
        # stateful-dataset support: snapshots taken at PRODUCTION time ride a
        # FIFO so the state reported by state_dict() matches the batch the
        # training loop actually holds — the lookahead + device prefetcher
        # consume the underlying dataset several batches ahead
        self._ds_state_fifo: collections.deque = collections.deque()
        self._last_ds_state = None

    @property
    def total_batch_size(self) -> Optional[int]:
        return self._total_batch_size

    def _spec_axes_size(self, dim: int) -> int:
        """Number of shards the given dim is split into on the mesh."""
        if self.sharding is None:
            return 1
        spec = self.sharding.spec
        entry = spec[dim] if len(spec) > dim else None
        if entry is None:
            return 1
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= self.sharding.mesh.shape[a]
        return size

    @property
    def _data_axes_size(self) -> int:
        return self._spec_axes_size(0)

    def _leaf_sharding(self, t):
        """Per-leaf sharding: rank-1 leaves drop the sequence axes."""
        if self.sharding is None:
            return None
        spec = self.sharding.spec
        if t.ndim >= len(spec):
            return self.sharding
        return NamedSharding(self.sharding.mesh, P(*spec[: t.ndim]))

    def _place(self, batch):
        """Assemble the global sharded batch array from host data.

        Rows are padded (by repeating the last sample) up to the next multiple
        of the data-shard count so the array shards evenly — the fixed-shape
        analogue of the reference's ``even_batches`` duplication
        (data_loader.py even_batches / utils/operations.py:805
        ``pad_input_tensors``); ``gather_for_metrics`` drops the duplicates
        using ``remainder``.
        """
        if self.sharding is None:
            return batch
        state = PartialState()
        n_shards = self._data_axes_size

        if state.num_processes > 1 and not hasattr(self, "_num_row_shards"):
            # distinct row slices being read across processes — processes
            # spanned by tp/cp read the SAME rows, so this can be < n_proc
            self._num_row_shards = data_shard_info(self.sharding)[0]
        num_row_shards = getattr(self, "_num_row_shards", 1)
        # a process's LOCAL rows only need to divide by the shards it itself
        # feeds (global divisibility = local divisor × num_row_shards)
        local_divisor = max(n_shards // num_row_shards, 1)

        def put(t):
            t = np.asarray(t)
            if t.ndim >= 1 and t.shape[0] % local_divisor != 0:
                missing = local_divisor - (t.shape[0] % local_divisor)
                t = np.concatenate([t, np.repeat(t[-1:], missing, axis=0)], axis=0)
            sharding = self._leaf_sharding(t)
            if state.num_processes > 1:
                global_shape = (t.shape[0] * num_row_shards,) + t.shape[1:]
                return jax.make_array_from_process_local_data(sharding, t, global_shape)
            return jax.device_put(t, sharding)

        from .ops.operations import recursively_apply

        return recursively_apply(put, batch)


    def _with_ds_snapshots(self, it):
        """When the dataset is stateful, record its state after producing each
        batch; consumed FIFO-aligned in _iter_with_gradient_state."""
        ds = self.dataset
        if not hasattr(ds, "state_dict"):
            return it

        def snapshotting():
            self._ds_state_fifo.clear()
            for batch in it:
                try:
                    self._ds_state_fifo.append(copy.deepcopy(ds.state_dict()))
                except Exception:  # noqa: BLE001 — protocol is best-effort
                    pass
                yield batch

        return snapshotting()

    def _close_prefetcher(self) -> None:
        """Shut down any live prefetch worker (abandoned iteration would
        otherwise leak the thread + its HBM-pinned staged batches)."""
        prefetcher = getattr(self, "_active_prefetcher", None)
        if prefetcher is not None:
            self._active_prefetcher = None
            prefetcher.close()

    def __del__(self):
        try:
            self._close_prefetcher()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def _iter_with_gradient_state(self, raw_iter):
        self.end_of_dataloader = False
        # re-iteration abandons any previous epoch's half-consumed iterator;
        # reap its prefetch worker before starting a new one
        self._close_prefetcher()
        self.gradient_state._add_dataloader(self)
        if self.rng_types is not None:
            synchronize_rng_states(self.rng_types, self.synchronized_generator)
        prefetcher = None
        try:
            if self.device_prefetch:
                prefetcher = _DevicePrefetcher(raw_iter, self._place)
                self._active_prefetcher = raw_iter = prefetcher
                place = lambda b: b
            else:
                place = self._place
            # one-batch lookahead so the LAST yield happens with
            # end_of_dataloader already True (drives grad-accum final sync)
            current = None
            have = False
            for nxt in raw_iter:
                if have:
                    # count-then-yield: a batch is "consumed" the moment the
                    # loop receives it, so a save_state taken while processing
                    # batch k resumes at k+1
                    self._position += 1
                    if self._ds_state_fifo:
                        self._last_ds_state = self._ds_state_fifo.popleft()
                    yield current
                current, have = nxt, True
            if have:
                self.end_of_dataloader = True
                self._position += 1
                if self._ds_state_fifo:
                    self._last_ds_state = self._ds_state_fifo.popleft()
                yield current
                # the consumer drained the epoch: a checkpoint taken after
                # this point must NOT replay-skip into the next epoch
                self._position = 0
        finally:
            # runs on normal exhaustion AND on GeneratorExit when the
            # consumer breaks/raises — the leak path close() exists for.
            # Close OUR prefetcher, not _active_prefetcher: a re-iteration
            # may already own a newer one this stale generator must not kill.
            if prefetcher is not None:
                prefetcher.close()
                if getattr(self, "_active_prefetcher", None) is prefetcher:
                    self._active_prefetcher = None
            self.gradient_state._remove_dataloader(self)
            self.iteration += 1


class DataLoaderShard(_BaseAcceleratedLoader):
    """Per-process loader over an already-sharded inner loader
    (reference data_loader.py:510-672)."""

    def __init__(
        self,
        inner: Iterable,
        sharding: Optional[NamedSharding] = None,
        device_prefetch: bool = True,
        rng_types: Optional[Sequence[str]] = None,
        synchronized_generator=None,
        batch_sampler: Optional[BatchSamplerShard] = None,
        total_dataset_length: Optional[int] = None,
        total_batch_size: Optional[int] = None,
        sampler=None,
    ):
        super().__init__(
            sharding,
            device_prefetch,
            rng_types,
            synchronized_generator,
            total_dataset_length,
            total_batch_size,
        )
        self.inner = inner
        self.batch_sampler = batch_sampler
        self.sampler = sampler
        self._skip_batches = 0

    @property
    def dataset(self):
        return getattr(self.inner, "dataset", self.inner)

    def set_epoch(self, epoch: int) -> None:
        """Propagate epoch for deterministic reshuffling
        (reference data_loader.py:622)."""
        if self.sampler is not None and hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(epoch)
        if hasattr(self.inner, "set_epoch"):
            self.inner.set_epoch(epoch)
        elif hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self):
        n = len(self.inner)
        return max(0, n - self._skip_batches)

    def __iter__(self):
        # remainder: number of duplicated samples in the final global batch
        if self.total_dataset_length is not None and self.total_batch_size:
            rem = self.total_dataset_length % self.total_batch_size
            self.remainder = rem if rem != 0 else -1
        # _skip_once is an ABSOLUTE resume position (it already includes any
        # skip_first_batches offset, since _position counts skipped batches);
        # summing the two would double-skip on resume
        skip = self._skip_once if self._skip_once else self._skip_batches
        self._skip_once = 0
        self._position = skip
        it = iter(self.inner)
        for _ in range(skip):
            next(it, None)
        yield from self._iter_with_gradient_state(self._with_ds_snapshots(it))

    def state_dict(self) -> dict:
        """EXACT resumable-iteration state (the sampler.bin role, reference
        checkpointing.py:154-179; torchdata StatefulDataLoader backing,
        reference data_loader.py:422-444): epoch + batches already consumed
        this epoch, plus the dataset's own state when it implements the
        stateful protocol (the iterable-dataset story)."""
        state = {
            "iteration": self.iteration,
            "skip_batches": self._skip_batches,
            "position": self._position,
            "epoch": getattr(self.sampler, "epoch", 0) if self.sampler is not None else 0,
        }
        # self-describing position: `position` counts GLOBAL batches of this
        # size, so an elastic resume on a different world can remap it
        # (elastic.remap_sampler_state) instead of guessing the old ratio
        if self.total_batch_size:
            state["total_batch_size"] = self.total_batch_size
        ds = self.dataset
        if self._last_ds_state is not None:
            state["dataset_state"] = self._last_ds_state
        elif hasattr(ds, "state_dict"):
            try:
                state["dataset_state"] = ds.state_dict()
            except Exception:  # noqa: BLE001 — stateful protocol is best-effort
                pass
        return state

    def load_state_dict(self, state: dict) -> None:
        self.iteration = state.get("iteration", 0)
        self._skip_batches = state.get("skip_batches", 0)
        if self.sampler is not None and hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(state.get("epoch", 0))
        ds = self.dataset
        if "dataset_state" in state and hasattr(ds, "load_state_dict"):
            # stateful dataset resumes itself — no skip replay needed
            ds.load_state_dict(state["dataset_state"])
        else:
            # deterministic replay: seeded samplers re-derive the same order
            # from (seed, epoch), so skipping `position` batches lands exactly
            # where the checkpoint was taken (also correct for deterministic
            # iterables, which are replayed then fast-forwarded)
            self._skip_once = state.get("position", 0)


class DataLoaderDispatcher(_BaseAcceleratedLoader):
    """Main-process-reads-all loader: process 0 iterates the full dataset and
    broadcasts each global batch; every process then holds the same global
    array (reference data_loader.py:723-1014 ``_fetch_batches``/``__iter__``).

    On single-controller JAX the "slice your shard" step of the reference is
    subsumed by the array's sharding: we broadcast host data then build the
    sharded global array.
    """

    def __init__(
        self,
        inner: Iterable,
        sharding: Optional[NamedSharding] = None,
        device_prefetch: bool = True,
        split_batches: bool = True,
        total_dataset_length: Optional[int] = None,
        total_batch_size: Optional[int] = None,
    ):
        super().__init__(
            sharding,
            device_prefetch,
            None,
            None,
            total_dataset_length,
            total_batch_size,
        )
        self.inner = inner
        self.split_batches = split_batches

    @property
    def dataset(self):
        return getattr(self.inner, "dataset", self.inner)

    def __len__(self):
        return len(self.inner)

    def _fetch(self):
        from .ops.operations import broadcast, broadcast_object_list, get_data_structure, initialize_tensors

        state = PartialState()
        if state.num_processes == 1:
            yield from iter(self.inner)
            return
        if state.is_main_process:
            it = iter(self.inner)
            while True:
                batch = next(it, None)
                stop = batch is None
                info = [None if stop else get_data_structure(batch), stop]
                broadcast_object_list(info)
                if stop:
                    return
                yield broadcast(batch, from_process=0)
        else:
            while True:
                info = broadcast_object_list([None, None])
                structure, stop = info
                if stop:
                    return
                batch = initialize_tensors(structure)
                yield broadcast(batch, from_process=0)

    def _place(self, batch):
        # every process holds the FULL batch after broadcast → plain device_put
        if self.sharding is None:
            return batch
        from .ops.operations import recursively_apply

        return recursively_apply(
            lambda t: jax.device_put(np.asarray(t), self._leaf_sharding(np.asarray(t))), batch
        )

    def __iter__(self):
        if self.total_dataset_length is not None and self.total_batch_size:
            rem = self.total_dataset_length % self.total_batch_size
            self.remainder = rem if rem != 0 else -1
        skip = self._skip_once
        self._skip_once = 0
        self._position = skip
        it = self._fetch()
        for _ in range(skip):
            next(it, None)
        yield from self._iter_with_gradient_state(self._with_ds_snapshots(it))

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.inner, "set_epoch"):
            self.inner.set_epoch(epoch)
        elif hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def state_dict(self) -> dict:
        """Exact resume state; rank-0 reads the data so the position (plus the
        dataset's own state when stateful) fully describes the stream."""
        state = {"iteration": self.iteration, "position": self._position}
        ds = self.dataset
        if self._last_ds_state is not None:
            state["dataset_state"] = self._last_ds_state
        elif hasattr(ds, "state_dict"):
            try:
                state["dataset_state"] = ds.state_dict()
            except Exception:  # noqa: BLE001
                pass
        return state

    def load_state_dict(self, state: dict) -> None:
        self.iteration = state.get("iteration", 0)
        ds = self.dataset
        if "dataset_state" in state and hasattr(ds, "load_state_dict"):
            ds.load_state_dict(state["dataset_state"])
        else:
            self._skip_once = state.get("position", 0)


# -------------------------------------------------------------- native loader
class _ArrayBatcher:
    """Minimal map-style batcher over a pytree-of-arrays dataset or a
    ``__getitem__``/``__len__`` dataset — the zero-torch native path."""

    def __init__(self, dataset, batch_sampler, collate_fn=None):
        self.dataset = dataset
        self.batch_sampler = batch_sampler
        self.collate_fn = collate_fn or default_collate

    def __len__(self):
        return len(self.batch_sampler)

    def set_epoch(self, epoch):
        if hasattr(self.batch_sampler, "set_epoch"):
            self.batch_sampler.set_epoch(epoch)

    def __iter__(self):
        for batch_indices in self.batch_sampler:
            if isinstance(self.dataset, dict):
                yield {k: np.asarray(v)[batch_indices] for k, v in self.dataset.items()}
            else:
                yield self.collate_fn([self.dataset[i] for i in batch_indices])


class _SimpleBatchSampler:
    """Chunk an index sampler into batches (torch BatchSampler equivalent)."""

    def __init__(self, sampler, batch_size: int, drop_last: bool = False):
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def set_epoch(self, epoch):
        if hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(epoch)

    def __len__(self):
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else math.ceil(n / self.batch_size)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch


# -------------------------------------------------------------------- factory
def prepare_data_loader(
    dataloader,
    mesh: Optional[Mesh] = None,
    batch_size: Optional[int] = None,
    shuffle: bool = False,
    seed: int = 0,
    drop_last: bool = False,
    collate_fn=None,
    split_batches: bool = False,
    even_batches: bool = True,
    dispatch_batches: Optional[bool] = None,
    device_prefetch: bool = True,
    rng_types: Optional[Sequence[str]] = None,
    batch_axes: Sequence[str] = ("dp_replicate", "dp_shard"),
    seq_axes: Sequence[str] = (),
    put_on_device: bool = True,
):
    """Turn a dataset/dataloader into a mesh-sharded loader
    (reference data_loader.py:1016-1330 ``prepare_data_loader``).

    Accepts, in decreasing order of "native-ness":
      1. a dict/pytree of numpy arrays (column store) — batched natively;
      2. any map-style dataset (``__len__``/``__getitem__``) — batched natively;
      3. a ``torch.utils.data.DataLoader`` — its dataset and sampler settings
         are extracted and re-wrapped with sharded sampling;
      4. any iterable of batches — sharded per-batch in stride mode.
    """
    state = PartialState()
    if mesh is None:
        from .state import AcceleratorState, is_initialized

        if is_initialized():
            mesh = AcceleratorState().get_device_mesh()
    sharding = (
        batch_sharding(mesh, batch_axes, seq_axes) if (mesh is not None and put_on_device) else None
    )

    # Data sharding happens at process granularity (each process feeds its
    # local devices); single-process SPMD feeds the whole global batch.
    # The shard index comes from the MESH, not the raw process index:
    # processes spanned by tp/cp/pp axes must read identical rows
    # (reference data_loader.py:1129-1165).
    if sharding is not None and state.num_processes > 1:
        num_shards, shard_index, _ = data_shard_info(sharding)
    else:
        num_shards = state.num_processes
        shard_index = state.process_index
    if dispatch_batches is None:
        dispatch_batches = False

    # -- torch DataLoader: unwrap
    if _is_torch_loader(dataloader):
        return _prepare_from_torch_loader(
            dataloader,
            sharding=sharding,
            num_shards=num_shards,
            shard_index=shard_index,
            split_batches=split_batches,
            even_batches=even_batches,
            dispatch_batches=dispatch_batches,
            device_prefetch=device_prefetch,
            rng_types=rng_types,
        )

    # -- native dataset paths
    dataset = dataloader
    if isinstance(dataset, dict) or hasattr(dataset, "__getitem__"):
        if batch_size is None:
            raise ValueError("batch_size is required when passing a dataset")
        length = (
            len(next(iter(dataset.values()))) if isinstance(dataset, dict) else len(dataset)
        )
        if shuffle:
            sampler = SeedableRandomSampler(length, seed=seed)
        else:
            sampler = range(length)
        global_batch = batch_size if split_batches else batch_size * num_shards

        if dispatch_batches:
            inner_bs = _SimpleBatchSampler(sampler, global_batch, drop_last)
            inner = _ArrayBatcher(dataset, inner_bs, collate_fn)
            return DataLoaderDispatcher(
                inner,
                sharding=sharding,
                device_prefetch=device_prefetch,
                total_dataset_length=length,
                total_batch_size=global_batch,
            )
        per_process = global_batch // num_shards
        base_sampler = _SimpleBatchSampler(sampler, per_process, drop_last)
        shard_sampler = (
            BatchSamplerShard(
                base_sampler,
                num_processes=num_shards,
                process_index=shard_index,
                split_batches=False,
                even_batches=even_batches,
            )
            if num_shards > 1
            else base_sampler
        )
        inner = _ArrayBatcher(dataset, shard_sampler, collate_fn)
        return DataLoaderShard(
            inner,
            sharding=sharding,
            device_prefetch=device_prefetch,
            rng_types=rng_types,
            batch_sampler=shard_sampler,
            sampler=sampler if shuffle else None,
            total_dataset_length=length,
            total_batch_size=global_batch,
        )

    # -- generic iterable of ready-made batches
    return DataLoaderShard(
        dataset,
        sharding=sharding,
        device_prefetch=device_prefetch,
        rng_types=rng_types,
    )


def _prepare_from_torch_loader(
    loader,
    sharding,
    num_shards,
    shard_index,
    split_batches,
    even_batches,
    dispatch_batches,
    device_prefetch,
    rng_types,
):
    """Re-wrap a torch DataLoader with sharded sampling, preserving its
    dataset/collate/workers (reference data_loader.py:1016-1128)."""
    import torch.utils.data as tud

    dataset = loader.dataset
    if isinstance(dataset, tud.IterableDataset):
        shard = IterableDatasetShard(
            dataset,
            batch_size=loader.batch_size or 1,
            drop_last=loader.drop_last,
            num_processes=num_shards,
            process_index=shard_index,
            split_batches=split_batches,
        )
        new_loader = tud.DataLoader(
            shard,
            batch_size=loader.batch_size,
            collate_fn=loader.collate_fn,
            num_workers=loader.num_workers,
        )
        return DataLoaderShard(
            _TorchBatchIterator(new_loader),
            sharding=sharding,
            device_prefetch=device_prefetch,
            rng_types=rng_types,
        )

    batch_sampler = loader.batch_sampler
    if dispatch_batches:
        # the torch loader's own batches ARE the broadcast global batches, so
        # its batch_size is the total batch size regardless of split_batches
        return DataLoaderDispatcher(
            _TorchBatchIterator(loader),
            sharding=sharding,
            device_prefetch=device_prefetch,
            total_dataset_length=len(dataset),
            total_batch_size=loader.batch_size or 1,
        )
    shard_sampler = BatchSamplerShard(
        batch_sampler,
        num_processes=num_shards,
        process_index=shard_index,
        split_batches=split_batches,
        even_batches=even_batches,
    )
    new_loader = tud.DataLoader(
        dataset,
        batch_sampler=shard_sampler,
        collate_fn=loader.collate_fn,
        num_workers=loader.num_workers,
        pin_memory=False,
    )
    total_bs = (loader.batch_size or 1) * (1 if split_batches else num_shards)
    return DataLoaderShard(
        _TorchBatchIterator(new_loader),
        sharding=sharding,
        device_prefetch=device_prefetch,
        rng_types=rng_types,
        batch_sampler=shard_sampler,
        total_dataset_length=len(dataset),
        total_batch_size=total_bs,
    )


class _TorchBatchIterator:
    """Adapter converting torch-tensor batches to numpy pytrees."""

    def __init__(self, loader):
        self.loader = loader

    def __len__(self):
        return len(self.loader)

    @property
    def dataset(self):
        return self.loader.dataset

    def set_epoch(self, epoch):
        sampler = getattr(self.loader, "batch_sampler", None)
        if sampler is not None and hasattr(sampler, "set_epoch"):
            sampler.set_epoch(epoch)

    def __iter__(self):
        from .ops.operations import recursively_apply

        def to_numpy(t):
            return t.numpy() if hasattr(t, "numpy") else np.asarray(t)

        for batch in self.loader:
            yield recursively_apply(
                to_numpy, batch, test_type=lambda x: hasattr(x, "numpy") or isinstance(x, np.ndarray)
            )


# ---------------------------------------------------------------------- skip
def skip_first_batches(dataloader, num_batches: int = 0):
    """Efficient mid-epoch resume: skip the first ``num_batches``
    (reference data_loader.py:1395-1473)."""
    if isinstance(dataloader, DataLoaderShard):
        dataloader._skip_batches = num_batches
        return dataloader

    class _Skipper:
        def __init__(self, inner, n):
            self.inner = inner
            self.n = n

        def __len__(self):
            return max(0, len(self.inner) - self.n)

        def __iter__(self):
            it = iter(self.inner)
            for _ in range(self.n):
                next(it, None)
            yield from it

    return _Skipper(dataloader, num_batches)
