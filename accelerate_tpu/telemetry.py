"""Non-blocking step telemetry: fused on-device health reductions, a
bounded deferred-readback ring, and an async tracker flusher.

The training loop's safety/observability hooks (``check_step_health``,
``Accelerator.log``) used to be host sync points: every call flushed the
async dispatch pipeline with a ``device_get`` — and with ``check_grads``
one blocking transfer *per gradient leaf*. That undoes the dispatch-
overhead wins the fused ``train_step`` exists for (a dispatch that the
host issues ahead of the device costs microseconds, a forced readback
waits for the whole step). Keeping
the host ahead of the device is the whole game; this module makes every
per-step host interaction cost ~zero steady-state step time:

* :func:`health_summary` — ONE jitted on-device reduction of the loss's
  and the whole grad-pytree's finiteness (plus the global grad norm,
  reusing the optimizer's clipping reduction when already computed) into
  a single tiny ``f32[3]`` array: one device→host transfer instead of N.
* :class:`DeferredReadbackRing` — a bounded ring (depth K): each step
  enqueues its device scalars and only the value from K steps ago is
  read back, so the host never blocks on the step it just dispatched and
  the pipeline stays full. Verdicts arrive with K-step latency.
* :class:`AsyncTrackerFlusher` — a background thread that materializes
  ``jax.Array`` metric values and writes tracker batches off the hot
  path; JSONL/TensorBoard writes are batched per wakeup.

Every telemetry readback in the package funnels through :func:`_fetch`
so tests can count device→host transfers by shimming one function.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import tracing
from .logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "health_summary",
    "read_summary",
    "StepHealth",
    "DeferredReadbackRing",
    "AsyncTrackerFlusher",
    "LatencyReservoir",
]

# sentinel for "no grad norm in this summary" — real norms are >= 0, and a
# NaN norm is data (it means the grads are non-finite), so -1 is unambiguous
_NORM_UNSET = -1.0


def _fetch(value):
    """THE telemetry device→host transfer point. All health-verdict and
    metric readbacks go through here — one shim to count transfers in
    tests, one place that documents where the host may block."""
    return np.asarray(jax.device_get(value))


@jax.jit
def _summarize(loss, grads, grad_norm):
    """Tree-reduce (loss, grads) finiteness + global grad norm into ONE
    f32[3] array: [loss_finite, grads_finite, grad_norm]. Runs as a single
    compiled program (cached per pytree structure), so the step's health
    costs one tiny kernel and one scalar transfer — never a per-leaf loop.
    """
    if loss is None:
        loss_ok = jnp.bool_(True)
    else:
        loss_ok = jnp.all(jnp.isfinite(jnp.asarray(loss, jnp.float32)))
    float_leaves = [
        g
        for g in jax.tree_util.tree_leaves(grads)
        if jnp.issubdtype(jnp.asarray(g).dtype, jnp.floating)
    ]
    grads_ok = jnp.bool_(True)
    for g in float_leaves:
        grads_ok = jnp.logical_and(grads_ok, jnp.all(jnp.isfinite(g)))
    if grad_norm is not None:
        norm = jnp.asarray(grad_norm, jnp.float32).reshape(())
    elif float_leaves:
        # same reduction as the optimizer's clip_by_global_norm — computed
        # here only when no caller already has it
        norm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in float_leaves)
        )
    else:
        norm = jnp.float32(_NORM_UNSET)
    return jnp.stack([loss_ok.astype(jnp.float32), grads_ok.astype(jnp.float32), norm])


def health_summary(loss=None, grads=None, grad_norm=None) -> jax.Array:
    """Fused on-device health reduction (see :func:`_summarize`). Returns
    a device ``f32[3]`` — NOT a host value: dispatching this is non-
    blocking; pair with :func:`read_summary` (or the ring) to realize it."""
    return _summarize(loss, grads, grad_norm)


class StepHealth(NamedTuple):
    """Host-side verdict for one step's telemetry summary."""

    step: int
    loss_finite: bool
    grads_finite: bool
    grad_norm: Optional[float]

    @property
    def healthy(self) -> bool:
        return self.loss_finite and self.grads_finite


def read_summary(summary, step: int) -> StepHealth:
    """Realize a :func:`health_summary` device array on the host (the one
    blocking point) and decode it."""
    vals = _fetch(summary)
    norm = float(vals[2])
    return StepHealth(
        step=step,
        loss_finite=bool(vals[0] != 0.0),
        grads_finite=bool(vals[1] != 0.0),
        grad_norm=None if norm == _NORM_UNSET else norm,
    )


class DeferredReadbackRing:
    """Bounded FIFO of in-flight device values.

    ``push(entry)`` enqueues this step's (still device-resident) scalars
    and returns the entries that have matured — those pushed ``depth``
    steps ago, which have almost certainly finished executing, so reading
    them back does not stall the dispatch pipeline. ``drain()``/
    ``popleft()`` empty the ring at epoch boundaries / shutdown."""

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = depth
        self._entries: collections.deque = collections.deque()

    def push(self, entry) -> list:
        self._entries.append(entry)
        matured = []
        while len(self._entries) > self.depth:
            matured.append(self._entries.popleft())
        return matured

    def popleft(self):
        return self._entries.popleft()

    def drain(self) -> list:
        out = list(self._entries)
        self._entries.clear()
        return out

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)


class LatencyReservoir:
    """Bounded sliding-window percentile estimator for request latencies
    (and any other per-event scalar): keeps the last ``size`` samples in a
    ring, computes p50/p99 over the window on demand. Thread-safe — the
    serving worker records while metric snapshots read. Memory is O(size)
    no matter how many requests flow through."""

    def __init__(self, size: int = 2048):
        if size < 1:
            raise ValueError(f"reservoir size must be >= 1, got {size}")
        self._samples: collections.deque = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._count = 0

    def add(self, value: float) -> None:
        with self._lock:
            self._samples.append(float(value))
            self._count += 1

    @property
    def count(self) -> int:
        """Total samples ever recorded (not just the retained window)."""
        return self._count

    def percentile(self, p: float) -> Optional[float]:
        with self._lock:
            if not self._samples:
                return None
            data = sorted(self._samples)
        idx = min(len(data) - 1, max(0, int(round(p / 100.0 * (len(data) - 1)))))
        return data[idx]

    def snapshot(self, prefix: str = "") -> dict:
        """p50/p99/max over the window + lifetime count, flat dict keyed
        ``<prefix>p50`` etc. — ready for ``GeneralTracker.log_batch``."""
        with self._lock:
            data = sorted(self._samples)
            count = self._count
        if not data:
            return {f"{prefix}count": count}
        pick = lambda p: data[min(len(data) - 1, int(round(p / 100.0 * (len(data) - 1))))]
        return {
            f"{prefix}count": count,
            f"{prefix}p50": pick(50),
            f"{prefix}p99": pick(99),
            f"{prefix}max": data[-1],
        }


def materialize_metrics(values: dict) -> dict:
    """Convert ``jax.Array`` metric values to host scalars/arrays (one
    :func:`_fetch` per device value). Python/numpy values pass through
    untouched so custom trackers see exactly what the user logged."""
    out = {}
    for key, val in values.items():
        if isinstance(val, jax.Array):
            host = _fetch(val)
            out[key] = host.item() if host.size == 1 else host
        else:
            out[key] = val
    return out


_STOP = object()


class AsyncTrackerFlusher:
    """Background tracker writer: the hot path only enqueues (values may
    contain device ``jax.Array`` scalars — no readback, no block); a
    daemon thread materializes them and hands per-tracker BATCHES to
    ``tracker.log_batch`` (one file write/flush per wakeup, not per step).

    A tracker exception never kills the training loop: it is recorded,
    remaining trackers still receive the batch, and the first error is
    re-raised from :meth:`flush`/:meth:`close` — so ``end_training``
    surfaces it after all pending writes were attempted."""

    # after the first record arrives, linger this long collecting more
    # before materializing/writing: turns per-step wakeups (each one GIL +
    # XLA-client contention with the dispatching thread) into one batch
    # write per interval. Bounded: a flush()/close() still drains promptly
    # because the linger only runs while nothing is joining the queue.
    COALESCE_S = 0.05

    def __init__(self, trackers, name: str = "tracker-flush"):
        self.trackers = trackers
        self._queue: queue.Queue = queue.Queue()
        self._errors: list[BaseException] = []
        self._closed = False
        self._draining = threading.Event()  # set while flush()/close() wait
        self._thread = threading.Thread(target=self._loop, name=name, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- hot path
    def submit(self, values: dict, step=None, log_kwargs: Optional[dict] = None):
        if self._closed:
            from .utils.fault import ComponentClosedError

            raise ComponentClosedError("AsyncTrackerFlusher is closed")
        self._queue.put((values, step, log_kwargs or {}))

    # ------------------------------------------------------------ background
    def _loop(self):
        while True:
            item = self._queue.get()
            if item is not _STOP and not self._draining.is_set():
                self._draining.wait(self.COALESCE_S)
            batch = [item]
            while True:  # opportunistic batching: drain whatever is queued
                try:
                    batch.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            stop = any(entry is _STOP for entry in batch)
            entries = [e for e in batch if e is not _STOP]
            if entries:
                self._write(entries)
            for _ in batch:
                self._queue.task_done()
            if stop:
                return

    def _write(self, entries):
        with tracing.span(
            "telemetry.flush_drain", batches=len(entries), trackers=len(self.trackers)
        ):
            materialized = []
            for values, step, log_kwargs in entries:
                try:
                    materialized.append((materialize_metrics(values), step, log_kwargs))
                except Exception as exc:  # noqa: BLE001 — never kill the thread
                    self._record(exc)
            for tracker in self.trackers:
                per_tracker = [
                    (values, step, kw.get(tracker.name, {}))
                    for values, step, kw in materialized
                ]
                try:
                    tracker.log_batch(per_tracker)
                except Exception as exc:  # noqa: BLE001
                    self._record(exc)

    def _record(self, exc: BaseException) -> None:
        if not self._errors:
            self._errors.append(exc)
        logger.warning(f"async tracker flush failed: {type(exc).__name__}: {exc}")

    # -------------------------------------------------------------- control
    def _raise_pending(self):
        if self._errors:
            raise self._errors.pop(0)

    # a queue.join() has no timeout parameter, so a flusher thread that died
    # (or a record stuck inside a tracker's write) would hang flush()/close()
    # — and with them end_training and the preemption emergency save —
    # forever. Bound the drain instead: give up after this many seconds, or
    # immediately once the worker thread is dead (nobody is left to call
    # task_done).
    DRAIN_TIMEOUT_S = 60.0

    def _drain_queue(self, timeout: Optional[float] = None) -> bool:
        """Bounded equivalent of ``queue.join()``: True when every queued
        record was processed, False on timeout or worker death."""
        deadline = time.monotonic() + (
            self.DRAIN_TIMEOUT_S if timeout is None else timeout
        )
        q = self._queue
        with q.all_tasks_done:
            while q.unfinished_tasks:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._thread.is_alive():
                    return False
                q.all_tasks_done.wait(min(remaining, 0.2))
        return True

    def flush(self) -> None:
        """Block (bounded) until every submitted record has been written or
        failed; re-raise the first deferred tracker error."""
        self._draining.set()
        try:
            if not self._drain_queue():
                logger.warning(
                    "tracker flush gave up after "
                    f"{self.DRAIN_TIMEOUT_S:.0f}s with "
                    f"{self._queue.unfinished_tasks} record(s) unwritten"
                )
        finally:
            self._draining.clear()
        self._raise_pending()

    def close(self) -> None:
        """Flush everything, stop the thread, surface deferred errors.
        Idempotent; bounded like :meth:`flush` so a dead or wedged flusher
        thread cannot hang ``end_training``."""
        if not self._closed:
            self._closed = True
            self._draining.set()
            self._queue.put(_STOP)
            if not self._drain_queue():
                logger.warning(
                    "tracker close gave up after "
                    f"{self.DRAIN_TIMEOUT_S:.0f}s with "
                    f"{self._queue.unfinished_tasks} record(s) unwritten"
                )
            self._thread.join(timeout=30)
        self._raise_pending()
