"""Resilient serving: deadline-aware dynamic batching with backpressure,
retry/backoff, circuit breaking, graceful degradation, and graceful drain.

The inference path used to be a bare compiled :func:`~accelerate_tpu
.inference.generate` call — fine for a notebook, not for the ROADMAP's
"heavy traffic from millions of users". The reference harness delegates
serving-shaped robustness to external engines (SURVEY §3.5); a TPU-native
framework must supply it itself, in the same single-controller style the
rest of the package uses: ONE Python worker thread owns dispatch, requests
are plain host-side objects, and the device only ever sees bucket-padded
batches that hit the per-model compiled-program LRU.

Robustness is the headline, not throughput (docs/serving.md):

* **Backpressure** — a bounded admission queue; full means a typed
  :class:`~accelerate_tpu.utils.fault.ServerOverloaded` NOW, not unbounded
  memory later.
* **Deadlines** — enforced at dequeue (a request that cannot finish in
  time is shed instead of wasting a batch slot — the estimate is an EWMA
  of recent batch times) and again at completion.
* **Retry** — transiently failed batches retry with exponential backoff +
  jitter; the retry budget is per batch, never per server.
* **Circuit breaker** — consecutive failed attempts (e.g. repeated
  RESOURCE_EXHAUSTED compiles) open the breaker: submissions fail fast
  with :class:`~accelerate_tpu.utils.fault.CircuitOpenError` while
  half-open probe batches test recovery.
* **Graceful degradation** — under sustained queue pressure per-request
  token budgets are clamped *before* anything is shed: cheaper batches
  drain a backlog faster than rejections do.
* **Graceful drain** — SIGTERM (via :func:`install_drain_handler` or the
  training-side preemption handler) stops admission, finishes in-flight
  batches, and rejects queued-but-unbatched requests with a retriable
  :class:`~accelerate_tpu.utils.fault.ServerDrainingError`.

Every lifecycle moment has a named :func:`~accelerate_tpu.utils.fault
.fault_point` (``serving_submit``, ``serving_before_batch``,
``serving_after_batch``, ``serving_before_reply``) so the test suite can
prove each failure mode, and queue depth / latency percentiles / shed-
timeout-retry-breaker counters flow through ``GeneralTracker.log_batch``.

Two scheduling modes (``ServingConfig.mode``, docs/serving.md):
``"static"`` (default, everything above) batches whole ``generate()``
calls at admission time; ``"continuous"`` replaces admission-time batching
with iteration-level scheduling over a slot-based KV arena
(:mod:`accelerate_tpu.engine`) — requests join and leave the running
decode batch every step, so mixed lengths/budgets/seeds stop fragmenting
batches and EOS'd rows stop burning decode steps. All robustness
semantics above apply to both modes.
"""

from __future__ import annotations

import collections
import random
import signal
import sys
import threading
import time
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import numpy as np

from . import perfwatch, tracing
from .logging import get_logger
from .telemetry import LatencyReservoir
from .tracing import MetricsRegistry
from .utils.dataclasses import ServingConfig
from .utils.fault import (
    PREEMPTION_EXIT_CODE,
    BatchExecutionError,
    CircuitOpenError,
    KVTransferError,
    ReplicaDeadError,
    RequestDeadlineExceeded,
    ServerDrainingError,
    ServerOverloaded,
    fault_point,
    preemption_requested,
)

logger = get_logger(__name__)

__all__ = [
    "InferenceServer",
    "ServingResult",
    "ServingMetrics",
    "install_drain_handler",
]


# ------------------------------------------------------------------- requests
@dataclass
class _Request:
    """One admitted generation request (internal; callers hold the Future)."""

    input_ids: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    deadline: Optional[float]  # absolute, server clock domain
    temperature: float
    top_k: Optional[int]
    top_p: Optional[float]
    eos_token_id: Optional[int]
    pad_token_id: Optional[int]
    seed: int
    submitted_at: float
    future: Future = field(default_factory=Future)
    # token budget after the degradation ladder clamped it (set at dequeue)
    effective_max_new_tokens: int = 0
    degraded: bool = False
    # continuous mode: a precomputed RemotePrefill (prefill/decode
    # disaggregation — the fleet's prefill workers ran the prompt forward
    # already; admission scatters it instead of re-running the forward)
    prefill: Any = None
    # request-scoped trace ID (tracing.new_trace_id); propagated fleet →
    # server → engine so one trace shows every hop including failovers
    trace_id: Optional[str] = None

    def group_key(self) -> tuple:
        """Requests sharing this key can ride one ``generate()`` batch: the
        sampling params are batch-uniform traced operands and the shapes
        (prompt length, token budget) are the compile key. ``seed`` joins
        the key only for sampled traffic (``temperature > 0``) — greedy
        decoding never consumes it, so keying greedy requests on seed would
        kill batching for nothing, while a sampled request's draws must
        come from *its* seed, not whichever request happened to lead the
        batch."""
        return (
            self.input_ids.shape[-1],
            self.effective_max_new_tokens,
            self.temperature,
            self.top_k,
            self.top_p,
            self.eos_token_id,
            self.pad_token_id,
            self.seed if self.temperature > 0.0 else None,
        )


@dataclass
class ServingResult:
    """What a completed request's Future resolves to."""

    tokens: np.ndarray  # (prompt_len + new,) int32 — this request's row
    latency_s: float
    batch_size: int  # real occupancy (before row padding)
    degraded: bool  # token budget was clamped by the pressure ladder
    # time-to-first-token. Static mode materializes the whole batch at once,
    # so TTFT == latency there; continuous mode records the host clock when
    # the slot's first token popped out of the deferred-readback ring.
    ttft_s: Optional[float] = None
    # which replica served it (None outside a fleet) — lets clients and the
    # router attribute latency without guessing
    replica_id: Optional[str] = None
    # span summary: where this request's latency went. Static mode has no
    # per-slot clocks, so queue_wait is latency minus in-batch time and
    # prefill_s stays None; continuous mode reads the occupant's stamps.
    queue_wait_s: Optional[float] = None
    prefill_s: Optional[float] = None
    decode_steps: int = 0
    # dispatch attempts minus one (filled by the fleet router on resolve;
    # a request served by its first replica reports 0)
    failover_count: int = 0


# ---------------------------------------------------------- future resolution
def resolve_future(
    future: Future, *, result=None, exception: Optional[BaseException] = None
) -> bool:
    """Resolve a client Future exactly once. Callers may ``cancel()`` a
    pending Future at any moment (client-side timeout), so every
    worker-side resolution must tolerate the done/cancelled race instead
    of dying on ``InvalidStateError``. Returns True when this call
    actually delivered the outcome.

    This is the ONLY place ``set_result``/``set_exception`` may appear in
    serving/fleet code — graftcheck G305 enforces it.
    """
    if future.done():
        return False
    try:
        if exception is not None:
            future.set_exception(exception)
        else:
            future.set_result(result)
        return True
    except InvalidStateError:  # lost the race to a concurrent cancel()
        return False


# -------------------------------------------------------------------- metrics
class ServingMetrics:
    """Thread-safe serving counters + latency reservoirs.

    A thin facade over :class:`tracing.MetricsRegistry` (one registry per
    server, prefix ``serving/``) — the registry owns the lock, the flush
    cadence, and the tracker bridge, so the periodic-flush logic is no
    longer duplicated here and in ``FleetMetrics``. Counters are
    monotonic; :meth:`snapshot` flattens everything into one
    ``serving/...`` dict suitable for ``GeneralTracker.log_batch`` — queue
    depth and breaker state are sampled at snapshot time."""

    _COUNTERS = (
        "submitted",
        "completed",
        "rejected_queue_full",
        "rejected_breaker",
        "rejected_draining",
        "shed_deadline",
        "completed_late",
        "retries",
        "batch_failures",
        "batches",
        "breaker_opens",
        "degraded",
        # continuous mode (ServingConfig.mode="continuous") only:
        "engine_inserts",  # requests admitted into arena slots
        "engine_steps",  # fused decode steps dispatched
        "engine_retired",  # occupants retired (EOS / budget / cancel)
        # a wire-shipped prefill lost its slot reservation between the
        # accepts_prefill check and the commit (epoch fence) — re-ran the
        # prompt forward locally instead
        "prefill_commit_fallbacks",
    )

    def __init__(self, clock=time.monotonic):
        self.registry = MetricsRegistry(
            prefix="serving/", counters=self._COUNTERS, clock=clock
        )
        self.latency = LatencyReservoir()  # seconds, accepted+completed only
        self.queue_wait = LatencyReservoir()  # seconds spent queued
        # ttft feeds the SLO controller's pressure signal: keep the window
        # short so p99 tracks CURRENT service, not ten seconds of history
        self.ttft = LatencyReservoir(size=256)  # seconds to first token
        self.registry.attach_reservoir("latency", self.latency)
        self.registry.attach_reservoir("queue_wait", self.queue_wait)
        self.registry.attach_reservoir("ttft", self.ttft)
        for name in (
            "queue_depth",
            "breaker_state",
            "kv_hbm_bytes",
            "kv_utilization",
            "prefix_hit_rate",
            "spec_acceptance_rate",
            "spec_tokens_per_step",
        ):
            self.registry.gauge(name, 0.0)

    def bump(self, name: str, by: int = 1) -> None:
        self.registry.bump(name, by)

    def gauge(self, name: str, value: float) -> None:
        self.registry.gauge(name, value)

    def __getitem__(self, name: str) -> int:
        return self.registry[name]

    def snapshot(self) -> dict:
        return self.registry.snapshot()


# ------------------------------------------------------------ circuit breaker
class _CircuitBreaker:
    """Classic three-state breaker over consecutive batch-attempt failures.

    CLOSED → (``threshold`` consecutive failures) → OPEN → (``reset_s``
    elapses) → HALF_OPEN (one probe batch) → CLOSED on success, OPEN on
    failure. State transitions happen on the worker thread; ``submit``
    only reads."""

    CLOSED, OPEN, HALF_OPEN = 0, 1, 2

    def __init__(self, threshold: int, reset_s: float, clock: Callable[[], float]):
        self.threshold = threshold
        self.reset_s = reset_s
        self._clock = clock
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self.opens = 0

    def state(self) -> int:
        """Current state; an OPEN breaker whose reset window has elapsed
        reports (and becomes) HALF_OPEN."""
        with self._lock:
            if (
                self._state == self.OPEN
                and self._clock() - self._opened_at >= self.reset_s
            ):
                self._state = self.HALF_OPEN
            return self._state

    @property
    def rejects_admission(self) -> bool:
        return self.state() == self.OPEN

    def seconds_until_probe(self) -> float:
        with self._lock:
            if self._state != self.OPEN:
                return 0.0
            return max(0.0, self.reset_s - (self._clock() - self._opened_at))

    def record_failure(self) -> bool:
        """Count one failed batch attempt; returns True when this failure
        opened (or re-opened) the breaker."""
        with self._lock:
            self._failures += 1
            was_open = self._state == self.OPEN
            if self._state == self.HALF_OPEN or self._failures >= self.threshold:
                self._state = self.OPEN
                self._opened_at = self._clock()
                if not was_open:
                    self.opens += 1
                    return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._state = self.CLOSED


# --------------------------------------------------------------------- server
class InferenceServer:
    """Turn concurrent ``submit()`` calls into dynamically batched,
    bucket-padded :func:`~accelerate_tpu.inference.generate` executions.

    One daemon worker thread owns the whole dispatch lifecycle (dequeue →
    shed → batch → execute → reply), so the device stream stays single-
    controller even under many submitting threads. Construction starts the
    worker; use as a context manager (or call :meth:`close`) to drain.

    Parameters
    ----------
    model:
        A prepared :class:`~accelerate_tpu.model.Model` (optionally sharded
        via :func:`~accelerate_tpu.inference.prepare_inference`).
    config:
        :class:`~accelerate_tpu.utils.dataclasses.ServingConfig`.
    generate_fn:
        Override the batch executor — signature of
        :func:`accelerate_tpu.inference.generate`, must return a
        ``(batch, prompt+new)`` array. Tests inject failures/latency here;
        ``None`` uses the real compiled path (and its per-model LRU).
    trackers:
        ``GeneralTracker`` instances receiving ``metrics.snapshot()``
        batches every ``config.metrics_interval_s`` (and once at drain).
    clock:
        Monotonic time source (injectable for deterministic tests).
    engine:
        Continuous mode only: inject a pre-built
        :class:`~accelerate_tpu.engine.ContinuousBatchingEngine` (tests);
        ``None`` builds one from the ``engine_*`` config knobs. In
        continuous mode ``generate_fn`` is inert — the engine owns the
        device programs.
    replica_id:
        Identity of this server inside a fleet (``None`` standalone).
        Stamped onto every typed :class:`~accelerate_tpu.utils.fault
        .ServingError` this server raises and onto every ``ServingResult`` so
        :class:`~accelerate_tpu.fleet.FleetRouter` can attribute failures
        and exclude the failed replica during failover without parsing
        message prose.
    """

    def __init__(
        self,
        model,
        config: Optional[ServingConfig] = None,
        *,
        generate_fn: Optional[Callable[..., Any]] = None,
        trackers: Sequence = (),
        clock: Callable[[], float] = time.monotonic,
        engine=None,
        replica_id: Optional[str] = None,
    ):
        self.model = model
        self.config = config or ServingConfig()
        self.replica_id = replica_id
        self.trackers = list(trackers)
        self._clock = clock
        self._generate_fn = generate_fn or self._default_generate
        self._engine = None
        if self.config.mode == "continuous":
            if engine is not None:
                self._engine = engine
            else:
                from .engine import ContinuousBatchingEngine

                self._engine = ContinuousBatchingEngine(
                    model,
                    slots=self.config.engine_slots,
                    max_len=self.config.engine_max_len,
                    prompt_bucket=self.config.engine_prompt_bucket,
                    readback_lag=self.config.engine_readback_lag,
                    kv_cache=self.config.kv_cache,
                    block_size=self.config.engine_block_size,
                    pool_blocks=self.config.engine_pool_blocks,
                    attention_impl=self.config.attention_impl,
                    spec=self.config.speculative,
                    spec_draft_len=self.config.spec_draft_len,
                    prefill_chunk=self.config.engine_prefill_chunk,
                    host_tier_bytes=self.config.kv_host_tier_bytes,
                    clock=clock,
                )
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._queue: collections.deque[_Request] = collections.deque()
        self._draining = False
        self._closed = False
        self._worker_error: Optional[BaseException] = None
        self._drained = threading.Event()
        self.metrics = ServingMetrics(clock=clock)
        self._breaker = _CircuitBreaker(
            self.config.breaker_threshold, self.config.breaker_reset_s, clock
        )
        self._batch_time_ewma = 0.0
        self._rng = random.Random(0)  # backoff jitter only
        self._worker = threading.Thread(
            target=self._serve_loop, name="inference-server", daemon=True
        )
        self._worker.start()
        # pull-based metrics endpoint (docs/observability.md), armed only
        # by ACCELERATE_METRICS_PORT / ObservabilityConfig — and only on a
        # STANDALONE server: fleet replicas are aggregated and exported by
        # the router, not scraped one socket each
        self._exporter = (
            perfwatch.maybe_exporter(self.metrics_snapshot)
            if replica_id is None else None
        )

    # ------------------------------------------------------------- admission
    def submit(
        self,
        input_ids,
        *,
        max_new_tokens: Optional[int] = None,
        deadline_s: Optional[float] = None,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token_id: Optional[int] = None,
        pad_token_id: Optional[int] = None,
        seed: int = 0,
        prefilled=None,
        arrival_s: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> Future:
        """Admit one request; returns a Future resolving to
        :class:`ServingResult` (or raising the typed serving error that
        ended it). Raises synchronously — *before* any queue mutation —
        when admission itself is refused:

        * :class:`ServerDrainingError` — draining/closed (retriable
          elsewhere);
        * :class:`CircuitOpenError` — breaker open, fail fast;
        * :class:`ServerOverloaded` — bounded queue full (backpressure).

        ``deadline_s`` is relative seconds from now (``None`` →
        ``config.default_deadline_s``).

        ``seed`` drives sampling (``temperature > 0``) deterministically:
        sampled requests only batch with requests sharing their seed (it is
        part of the batching group key), so another request's seed is never
        used for this request's draws. A row's draw still depends on its
        position inside the executed batch, so bitwise reproducibility
        additionally requires the same batch composition. Greedy requests
        (``temperature == 0``) ignore ``seed`` entirely.

        ``prefilled`` (continuous mode, fleet-internal) carries a
        :class:`~accelerate_tpu.engine.RemotePrefill` computed by a
        dedicated prefill worker; admission scatters it into a slot with
        the cheap commit-only program instead of re-running the prompt
        forward on the decode thread.

        ``arrival_s`` (fleet-internal) back-dates ``submitted_at`` to the
        request's *original* arrival on this server's clock domain, so
        latency and TTFT stay honest when a fleet router re-submits the
        request after a failover or a remote prefill — without it, every
        hop would reset the clock and under-report client-observed
        latency. Deadlines are unaffected (``deadline_s`` is always
        relative to now).

        ``trace_id`` joins this request to an existing trace (a fleet
        router submits with the root trace it minted); standalone servers
        mint one per request when the tracer is enabled so every span the
        request touches shares one ID.
        """
        fault_point("serving_submit", replica=self.replica_id)
        if self._closed or self._draining or preemption_requested():
            self.metrics.bump("rejected_draining")
            raise ServerDrainingError(
                self._drain_reason(), replica_id=self.replica_id,
                retry_after_s=0.0,  # another replica can take it NOW
            )
        if self._breaker.rejects_admission:
            self.metrics.bump("rejected_breaker")
            raise CircuitOpenError(
                "circuit breaker open after repeated batch failures; retry "
                f"in {self._breaker.seconds_until_probe():.2f}s",
                replica_id=self.replica_id,
                retry_after_s=self._breaker.seconds_until_probe(),
            )
        ids = np.asarray(input_ids, dtype=np.int32)
        if ids.ndim == 2:
            if ids.shape[0] != 1:
                raise ValueError(
                    "submit() takes ONE request; for many rows call submit "
                    f"per row (got shape {ids.shape})"
                )
            ids = ids[0]
        if ids.ndim != 1 or ids.shape[0] == 0:
            raise ValueError(f"input_ids must be a non-empty 1-D prompt, got {ids.shape}")
        if self._engine is not None:
            # arena fit is a structural property of the request — reject at
            # the door (synchronously, like the shape checks above) instead
            # of parking a Future that can only ever fail
            self._engine.validate_request(
                ids.shape[0], max_new_tokens or self.config.default_max_new_tokens
            )
            if hasattr(self._engine, "validate_prompt"):  # injected engines need not
                self._engine.validate_prompt(ids)
            if self.config.kv_prefetch and hasattr(self._engine, "prefetch"):
                # admission-time async prefetch: start the host-tier ->
                # device copy of any spilled prefix NOW, on the submitter's
                # thread, so the payload is resident (or in flight) by the
                # time the decode thread admits the request. hasattr-gated:
                # injected engines (fleet benches, tests) need not grow the
                # long-context surface
                self._engine.prefetch(ids)
        if prefilled is not None and self._engine is None:
            raise ValueError(
                "prefilled= requires mode='continuous' (no slot engine to "
                "commit the precomputed prefill into)"
            )
        now = self._clock()
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        req = _Request(
            input_ids=ids,
            max_new_tokens=max_new_tokens or self.config.default_max_new_tokens,
            deadline=(now + deadline_s) if deadline_s is not None else None,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_token_id=eos_token_id,
            pad_token_id=pad_token_id,
            seed=seed,
            submitted_at=arrival_s if arrival_s is not None else now,
            prefill=prefilled,
            trace_id=trace_id
            or (tracing.new_trace_id() if tracing.get_tracer().enabled else None),
        )
        with self._wake:
            if self._draining or self._closed:
                self.metrics.bump("rejected_draining")
                raise ServerDrainingError(
                    self._drain_reason(), replica_id=self.replica_id,
                    retry_after_s=0.0,
                )
            if len(self._queue) >= self.config.max_queue:
                self.metrics.bump("rejected_queue_full")
                hint = self._retry_after_hint(len(self._queue))
                raise ServerOverloaded(
                    f"admission queue full ({self.config.max_queue}); apply "
                    f"backpressure and resubmit in ~{hint:.2f}s",
                    replica_id=self.replica_id,
                    retry_after_s=hint,
                )
            self._queue.append(req)
            self.metrics.bump("submitted")
            self.metrics.gauge("queue_depth", len(self._queue))
            self._wake.notify()
        return req.future

    def generate(self, input_ids, *, timeout: Optional[float] = None, **kwargs):
        """Blocking convenience wrapper: ``submit(...).result().tokens``."""
        return self.submit(input_ids, **kwargs).result(timeout=timeout).tokens

    # ------------------------------------------------------------- lifecycle
    def _drain_reason(self) -> str:
        if self._worker_error is not None:
            return (
                "serving worker died "
                f"({type(self._worker_error).__name__}: {self._worker_error})"
                " — this replica cannot serve; resubmit to another replica"
            )
        return "server is draining — resubmit to another replica"

    # Race-safe Future resolution (module-level so fleet.py shares it and
    # graftcheck G305 has one blessed implementation to point at).
    _resolve = staticmethod(resolve_future)

    @property
    def draining(self) -> bool:
        return self._draining or self._closed

    @property
    def engine(self):
        """The continuous-mode slot engine (``None`` in static mode). The
        fleet's prefill workers reach :meth:`~accelerate_tpu.engine
        .ContinuousBatchingEngine.prefill_remote` through this; everything
        else on the engine belongs to the serving worker thread."""
        return self._engine

    def kv_prefix_digest(self) -> Optional[dict]:
        """The engine's KV prefix-registry digest
        (:meth:`~accelerate_tpu.engine.ContinuousBatchingEngine
        .kv_prefix_digest`) — collected by the fleet prober alongside
        ``health()`` to drive KV-affinity placement. ``None`` in static
        mode (no prefix registry to gossip)."""
        if self._engine is None:
            return None
        fn = getattr(self._engine, "kv_prefix_digest", None)
        return fn() if fn is not None else None

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def health(self) -> dict:
        """One cheap, lock-light health sample for routers and probers —
        no device work, no tracker I/O:

        * ``draining`` — admission is (or is about to be) stopped;
        * ``worker_alive`` — the serving worker thread is running;
        * ``worker_error`` — exception type name that killed the worker
          (``None`` while healthy);
        * ``breaker_state`` — 0 CLOSED / 1 OPEN / 2 HALF_OPEN;
        * ``queue_depth`` / ``queue_free`` — admission backlog and
          remaining bounded-queue room;
        * ``inflight`` — live engine slots (continuous) — static mode
          reports 0 (in-flight state lives inside the executing batch);
        * ``batch_ewma_s`` — recent per-batch (static) / per-step
          (continuous) execution time, the placement cost estimate;
        * ``mode`` / ``replica_id`` — identity.
        """
        depth = self.queue_depth()
        return {
            "replica_id": self.replica_id,
            "mode": self.config.mode,
            "draining": self.draining or preemption_requested(),
            "worker_alive": self._worker.is_alive(),
            "worker_error": (
                type(self._worker_error).__name__
                if self._worker_error is not None else None
            ),
            "breaker_state": self._breaker.state(),
            "queue_depth": depth,
            "queue_free": max(0, self.config.max_queue - depth),
            "inflight": self._engine.live_count() if self._engine is not None else 0,
            "batch_ewma_s": self._batch_time_ewma,
        }

    def metrics_snapshot(self) -> dict:
        """One flat metrics dict for exporters and fleet aggregation:
        the unified registry snapshot plus the process perf observatory
        (``perf/<program>/...``). Engine gauges are re-ingested HERE, not
        only per worker tick, so an idle replica's KV utilization, prefix
        hit rate and spec acceptance stay current in every scrape (the
        registry is thread-safe; ``engine.stats()`` reads host counters
        only — same cross-thread discipline as :meth:`health`)."""
        if self._engine is not None:
            self._sync_kv_gauges()
        out = self.metrics.registry.snapshot()
        out.update(perfwatch.get_watch().snapshot())
        return out

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admission, finish the in-flight batch, reject everything
        still queued with a retriable :class:`ServerDrainingError`. Returns
        True when the worker exited within ``timeout`` (default
        ``config.drain_timeout_s``)."""
        with self._wake:
            self._draining = True
            self._wake.notify_all()
        timeout = self.config.drain_timeout_s if timeout is None else timeout
        done = self._drained.wait(timeout)
        if not done:
            logger.warning(
                "serving drain did not finish within %.1fs (in-flight batch "
                "still executing)", timeout,
            )
        return done

    def close(self, drain: bool = True, timeout: Optional[float] = None) -> bool:
        """Drain (unless ``drain=False`` — then queued requests are still
        rejected, we just don't wait for the in-flight batch) and stop the
        worker. Idempotent."""
        done = self.drain(timeout if drain else 0.0)
        self._closed = True
        # Bounded join so close() actually retires the worker thread
        # (graftcheck G304) — unless close() is running *on* the worker
        # (a request callback closing its own server) where joining
        # yourself deadlocks.
        if self._worker is not threading.current_thread():
            self._worker.join(timeout=self.config.drain_timeout_s)
        if self._exporter is not None:
            self._exporter.close()
            self._exporter = None
        if self.trackers:
            self._flush_metrics(force=True)
        return done

    def __enter__(self) -> "InferenceServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # ----------------------------------------------------------- worker loop
    def _serve_loop(self) -> None:
        try:
            if self._engine is not None:
                self._loop_continuous()
            else:
                self._loop_static()
        except BaseException as exc:  # noqa: BLE001 — a dead worker must not hang clients
            # stop admission FIRST: nothing consumes the queue anymore, so a
            # later submit() must fail fast instead of parking a Future that
            # can never resolve
            with self._lock:
                self._worker_error = exc
                self._draining = True
            logger.exception("serving worker died; failing queued requests")
            # postmortem: persist the last N seconds of spans so the death
            # is debuggable after the process is gone
            tracing.flight_dump("worker_death")
            raise
        finally:
            with self._lock:
                self._draining = True
            if self._engine is not None:
                # normal drain retires everyone, so this is empty; a worker
                # death mid-flight leaves occupants whose tokens can no
                # longer be delivered — fail them, never strand them
                for occ in self._engine.reset():
                    self._resolve(
                        occ.tag.future,
                        exception=ReplicaDeadError(
                            "serving worker exited with this request still "
                            "in a decode slot",
                            replica_id=self.replica_id,
                        ),
                    )
            self._reject_queued()
            self._drained.set()
            self._flush_metrics(force=True)

    def _loop_static(self) -> None:
        """PR 3 semantics: admission-time dynamic batching of whole
        ``generate()`` calls."""
        while True:
            with self._wake:
                while not self._queue and not self._draining:
                    if preemption_requested():
                        self._draining = True
                        break
                    if self._flush_due():
                        break  # emit below, after releasing the lock
                    self._wake.wait(timeout=0.05)
                if self._draining or preemption_requested():
                    self._draining = True
                    return
            # flush with the lock released — a slow tracker must never
            # stall submit() or worker wakeups
            self._flush_metrics()
            st = self._breaker.state()
            if st == _CircuitBreaker.OPEN:
                # fail fast is submit()'s job; here just shed requests
                # whose deadline will pass before the next probe
                self._shed_expired()
                time.sleep(min(0.01, max(self._breaker.seconds_until_probe(), 0.001)))
                continue
            batch = self._collect_batch(
                probe=(st == _CircuitBreaker.HALF_OPEN)
            )
            if batch:
                self._execute(batch)

    def _loop_continuous(self) -> None:
        """Iteration-level scheduler over the slot engine: each pass retires
        finished slots, admits queued requests into freed slots (interleaved
        prefill), dispatches one fused decode step, and sheds mid-flight
        deadline misses. Draining stops admission but keeps stepping until
        every in-flight slot retires — the continuous analogue of static
        mode's "finish the in-flight batch"."""
        eng = self._engine
        while True:
            with self._wake:
                while (
                    not self._queue
                    and eng.live_count() == 0
                    and not self._draining
                    and not preemption_requested()
                    and not self._flush_due()
                ):
                    self._wake.wait(timeout=0.05)
                if self._draining or preemption_requested():
                    self._draining = True
                    if eng.live_count() == 0:
                        return  # queued requests rejected by the finally
            # one pass of the scheduler, never opened while the loop sleeps
            # on _wake above: admission, the decode dispatch, the readback
            # and the replies nest under it by the span's parent field
            with tracing.span(
                "serving.tick", queue_depth=len(self._queue),
                live=eng.live_count(),
            ) as tick:
                self._flush_metrics()
                st = self._breaker.state()
                if st == _CircuitBreaker.OPEN:
                    # engine failures reset the arena, so an open breaker means
                    # no live occupants: shed hopeless queued requests and wait
                    # out the probe window like static mode
                    self._shed_expired()
                    if eng.live_count() == 0:
                        time.sleep(
                            min(0.01, max(self._breaker.seconds_until_probe(), 0.001))
                        )
                        continue
                elif not self._draining:
                    tick.set("admitted", self._admit_slots(
                        probe=(st == _CircuitBreaker.HALF_OPEN)
                    ))
                self._engine_tick()

    # ------------------------------------------------- continuous scheduling
    def _estimated_completion_s(self, budget: int) -> float:
        """Continuous mode: the EWMA tracks per-decode-step time, so a
        request's completion estimate scales with its token budget."""
        return self._batch_time_ewma * max(1, budget)

    def _admit_slots(self, probe: bool = False) -> int:
        """Admit queued requests into free arena slots. Each admission is an
        interleaved ``prefill_insert`` program; live slots keep their state
        and simply decode alongside the newcomer on the next step. ``probe``
        (half-open breaker) admits at most one — risk the minimum. Returns
        how many were admitted."""
        eng = self._engine
        limit = 1 if probe else eng.free_slots()
        admitted = 0
        while admitted < limit and eng.free_slots() > 0:
            with self._wake:
                if not self._queue:
                    break
                now = self._clock()
                req = self._queue.popleft()
                level = self._degrade_level(len(self._queue) + 1)
                self.metrics.gauge("queue_depth", len(self._queue))
            if (
                req.deadline is not None
                and now + self._estimated_completion_s(req.max_new_tokens)
                > req.deadline
            ):
                self._shed(req, now)
                continue
            # the ladder clamps this request's SLOT budget — the whole point
            # of iteration-level scheduling is that degradation never
            # touches anyone else's slot
            self._clamp_budget(req, level)
            # paged KV: a free slot is not enough — the request's blocks
            # (net of copy-on-write prefix hits) must be free too. Requeue
            # at the head (FIFO order preserved) and stop admitting; blocks
            # free as live slots retire, so the next tick retries.
            if not eng.can_admit(req.input_ids, req.effective_max_new_tokens):
                with self._wake:
                    self._queue.appendleft(req)
                    self.metrics.gauge("queue_depth", len(self._queue))
                break
            if req.degraded:
                self.metrics.bump("degraded")
            try:
                fault_point("serving_before_batch", replica=self.replica_id)
                with tracing.span(
                    "serving.admit",
                    trace_id=req.trace_id,
                    queue_wait_s=max(0.0, now - req.submitted_at),
                    degraded=req.degraded,
                ) as sp:
                    committed = False
                    if (
                        req.prefill is not None
                        and req.effective_max_new_tokens <= req.prefill.max_new_tokens
                        and getattr(eng, "accepts_prefill", lambda _p: False)(req.prefill)
                    ):
                        # disaggregated path: the prompt forward already ran
                        # on a prefill worker — scatter it (commit-only
                        # program)
                        sp.set("path", "insert_prefilled")
                        try:
                            eng.insert_prefilled(
                                req.prefill,
                                max_new_tokens=req.effective_max_new_tokens,
                                tag=req,
                            )
                            committed = True
                        except KVTransferError:
                            # a wire-shipped prefill's slot reservation went
                            # stale between accepts_prefill and the commit
                            # (epoch fence) — the REQUEST is fine: re-run
                            # the prompt forward locally below
                            self.metrics.bump("prefill_commit_fallbacks")
                    if not committed:
                        pre = req.prefill
                        if (
                            pre is not None
                            and getattr(pre, "reservation", None) is not None
                        ):
                            # free a still-fresh reservation NOW (e.g. the
                            # budget clamp rejected the prefill) instead of
                            # holding the slot until the TTL reaper
                            eng.release_reservation(*pre.reservation)
                        sp.set("path", "insert")
                        eng.insert(
                            req.input_ids,
                            max_new_tokens=req.effective_max_new_tokens,
                            temperature=req.temperature,
                            top_k=req.top_k,
                            top_p=req.top_p,
                            eos_token_id=req.eos_token_id,
                            pad_token_id=req.pad_token_id,
                            seed=req.seed,
                            tag=req,
                        )
            except BaseException as exc:  # noqa: BLE001 — classified below
                if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                    self._fail_batch(
                        [req], exc, "worker interrupted mid-insert",
                        err_cls=ReplicaDeadError,
                    )
                    raise
                self._engine_failure(exc, also_fail=req)
                return admitted
            self.metrics.bump("engine_inserts")
            admitted += 1
        return admitted

    def _engine_tick(self) -> None:
        """One fused decode step + deferred-ring poll + retirement replies +
        mid-flight deadline shed."""
        eng = self._engine
        if eng.live_count() == 0:
            # nothing decoding; flush any stale ring entries (all-cancelled
            # slots) so they don't pin device arrays
            self._reply_retired(eng.poll(force=True), 0.0)
            return
        with self._wake:
            depth = len(self._queue)
        self._apply_spec_degradation(self._degrade_level(depth))
        try:
            t0 = self._clock()
            eng.step()
            retired = eng.poll()
            dt = self._clock() - t0
            fault_point("serving_after_batch", replica=self.replica_id)
        except BaseException as exc:  # noqa: BLE001 — classified below
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                self._fail_batch(
                    [o.tag for o in eng.reset()], exc,
                    "worker interrupted mid-step", err_cls=ReplicaDeadError,
                )
                raise
            self._engine_failure(exc)
            return
        self.metrics.bump("engine_steps")
        self._sync_kv_gauges()
        self._breaker.record_success()
        self._batch_time_ewma = (
            dt if self._batch_time_ewma == 0.0
            else 0.8 * self._batch_time_ewma + 0.2 * dt
        )
        self._reply_retired(retired, dt)
        # mid-flight deadline enforcement: a slot that can no longer make
        # its deadline frees immediately for the next queued request
        now = self._clock()
        for occ in eng.occupants():
            req = occ.tag
            if req.deadline is not None and now > req.deadline:
                eng.cancel(occ)
                self.metrics.bump("engine_retired")
                if self._resolve(
                    req.future,
                    exception=RequestDeadlineExceeded(
                        f"deadline passed {now - req.deadline:.3f}s ago "
                        "mid-decode — slot freed for queued traffic",
                        replica_id=self.replica_id,
                    ),
                ):
                    self.metrics.bump("shed_deadline")

    def _reply_retired(self, retired: list, dt: float) -> None:
        """Resolve futures of occupants the deferred ring just retired.
        Guarded like static mode's reply epilogue: the tokens exist, so any
        failure here must fail THESE requests, not strand them."""
        if not retired:
            return
        with tracing.span("serving.reply", retired=len(retired)):
            self._reply(retired)

    def _reply(self, retired: list) -> None:
        reqs = [occ.tag for occ in retired]
        try:
            fault_point("serving_before_reply", replica=self.replica_id)
            now = self._clock()
            occupancy = self._engine.live_count() + len(retired)
            for occ in retired:
                req = occ.tag
                self.metrics.bump("engine_retired")
                if req.deadline is not None and now > req.deadline:
                    if self._resolve(
                        req.future,
                        exception=RequestDeadlineExceeded(
                            f"decode finished {now - req.deadline:.3f}s past "
                            "the deadline",
                            replica_id=self.replica_id,
                        ),
                    ):
                        self.metrics.bump("completed_late")
                    continue
                latency = now - req.submitted_at
                ttft = (
                    occ.first_token_s - req.submitted_at
                    if occ.first_token_s is not None
                    else latency
                )
                delivered = self._resolve(
                    req.future,
                    result=ServingResult(
                        tokens=occ.output_row(),
                        latency_s=latency,
                        batch_size=occupancy,
                        degraded=req.degraded,
                        ttft_s=max(0.0, ttft),
                        replica_id=self.replica_id,
                        queue_wait_s=max(0.0, occ.inserted_s - req.submitted_at),
                        prefill_s=(
                            max(0.0, occ.first_token_s - occ.inserted_s)
                            if occ.first_token_s is not None
                            else None
                        ),
                        decode_steps=int(getattr(occ, "decode_steps", 0)),
                    ),
                )
                if delivered:
                    self.metrics.bump("completed")
                    self.metrics.latency.add(latency)
                    self.metrics.ttft.add(max(0.0, ttft))
                    self.metrics.queue_wait.add(
                        max(0.0, occ.inserted_s - req.submitted_at)
                    )
        except BaseException as exc:  # noqa: BLE001 — never strand a retiree
            self._fail_batch(reqs, exc, "decode finished but the reply failed")
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            logger.exception(
                "continuous reply epilogue failed; the retired slots' "
                "outstanding futures were failed with BatchExecutionError"
            )

    def _sync_kv_gauges(self) -> None:
        """Publish the engine's KV-cache health (pool HBM footprint, live-vs-
        reserved token utilization, prefix-cache hit rate) and speculative-
        decoding acceptance (acceptance rate, emitted tokens per verify
        step) as serving gauges, refreshed every tick."""
        stats = self._engine.stats()
        # the full engine stats tree also lands in the unified registry
        # (flattened to serving/engine/... gauges) so one snapshot carries
        # all three former surfaces
        self.metrics.registry.ingest(stats, prefix="engine")
        kv = stats.get("kv")
        if kv:
            self.metrics.gauge("kv_hbm_bytes", kv.get("hbm_bytes", 0))
            self.metrics.gauge("kv_utilization", kv.get("utilization", 0.0))
            hits = kv.get("prefix_hits", 0)
            misses = kv.get("prefix_misses", 0)
            if hits + misses:
                self.metrics.gauge("prefix_hit_rate", hits / (hits + misses))
            if "host_tier_bytes" in kv:
                # host-RAM spill tier economics (docs/serving.md metric table)
                self.metrics.gauge("kv_host_tier_bytes", kv["host_tier_bytes"])
                self.metrics.gauge("kv_host_tier_blocks", kv.get("host_tier_blocks", 0))
                self.metrics.gauge("kv_restore_hits", kv.get("restore_hits", 0))
                self.metrics.gauge("kv_restore_bytes", kv.get("restore_bytes", 0))
                self.metrics.gauge("kv_spill_bytes", kv.get("spill_bytes", 0))
        if "prefill_chunks_pending" in stats:
            self.metrics.gauge(
                "prefill_chunks_pending", stats["prefill_chunks_pending"]
            )
        spec = stats.get("spec")
        if spec and spec.get("mode") != "off":
            self.metrics.gauge(
                "spec_acceptance_rate", spec.get("acceptance_rate", 0.0)
            )
            self.metrics.gauge(
                "spec_tokens_per_step", spec.get("tokens_per_step", 0.0)
            )

    def _engine_failure(self, exc: BaseException, also_fail=None) -> None:
        """An engine program failed. Device state is donated across programs
        so a failed dispatch cannot be replayed — the blast radius is every
        in-flight slot (documented trade-off vs static mode's per-batch
        retry): fail their futures, rebuild the arena, and let the breaker
        gate re-admission."""
        self.metrics.bump("batch_failures")
        opened = self._breaker.record_failure()
        if opened:
            self.metrics.bump("breaker_opens")
            logger.warning(
                "circuit breaker OPEN after %d consecutive engine failures "
                "(last: %s)", self.config.breaker_threshold, exc,
            )
        orphans = self._engine.reset()
        victims = [o.tag for o in orphans]
        if also_fail is not None:
            victims.append(also_fail)
        if victims:
            self._fail_batch(
                victims, exc,
                f"engine program failed; {len(victims)} in-flight slot(s) lost",
            )
        logger.warning(
            "engine failure reset the KV arena (%d in-flight request(s) "
            "failed): %s: %s", len(victims), type(exc).__name__, exc,
        )

    def _estimated_batch_s(self) -> float:
        return self._batch_time_ewma

    def _retry_after_hint(self, depth: int) -> float:
        """Backpressure hint attached to :class:`ServerOverloaded`: the
        estimated wall time until a queue slot frees, derived from the
        batch-time EWMA and the current depth. Static mode drains the
        queue ``max_batch_size`` requests per EWMA batch; continuous mode
        frees a slot roughly every ``engine_slots``-th share of a retiring
        budget (the EWMA there is per-step, so scale by the degraded token
        budget). A cold EWMA falls back to the batch window. Clamped so a
        pathological EWMA can never tell clients to go away for minutes."""
        ewma = self._batch_time_ewma
        if self._engine is not None:
            per_free = (ewma or 0.01) * max(1, self.config.degraded_max_new_tokens)
            per_free /= max(1, self.config.engine_slots)
        else:
            waves = (max(1, depth) + self.config.max_batch_size - 1) // max(
                1, self.config.max_batch_size
            )
            per_free = (ewma or self.config.batch_window_s or 0.01) * waves
        return float(min(5.0, max(1e-3, per_free)))

    def _degrade_level(self, depth: int) -> int:
        frac = depth / self.config.max_queue
        if frac >= self.config.degrade_hard_fraction:
            return 2
        if frac >= self.config.degrade_queue_fraction:
            return 1
        return 0

    def _apply_spec_degradation(self, level: int) -> None:
        """First rung of the continuous degradation ladder: under queue
        pressure, shrink the speculative draft limit before touching anyone's
        token budget (level 1 halves it, level 2 disables drafting). Wasted
        draft compute is the cheapest thing to shed, and the clamp is free —
        the verify program stays padded to the configured draft length, so
        no recompile. Restores the full limit once pressure subsides."""
        eng = self._engine
        if eng is None or getattr(eng, "spec", None) is None:
            return
        full = self.config.spec_draft_len
        if level >= 2:
            eng.set_spec_draft_limit(0)
        elif level == 1:
            eng.set_spec_draft_limit(max(1, full // 2))
        else:
            eng.set_spec_draft_limit(full)

    def _clamp_budget(self, req: _Request, level: int) -> None:
        budget = req.max_new_tokens
        if level == 1:
            budget = min(budget, self.config.degraded_max_new_tokens)
        elif level == 2:
            budget = min(budget, max(1, self.config.degraded_max_new_tokens // 2))
        req.degraded = budget < req.max_new_tokens
        req.effective_max_new_tokens = budget

    def _shed(self, req: _Request, now: float) -> None:
        shed = self._resolve(
            req.future,
            exception=RequestDeadlineExceeded(
                f"deadline passed {now - req.deadline:.3f}s ago at dequeue "
                f"(estimated batch time {self._estimated_batch_s():.3f}s) — "
                "shed instead of wasting a batch slot",
                replica_id=self.replica_id,
            ),
        )
        if shed:
            self.metrics.bump("shed_deadline")

    def _shed_expired(self) -> None:
        """Drop queued requests that can no longer make their deadline
        (used while the breaker is open so clients fail fast)."""
        now = self._clock()
        with self._lock:
            keep: collections.deque[_Request] = collections.deque()
            while self._queue:
                req = self._queue.popleft()
                if req.deadline is not None and now + self._estimated_batch_s() > req.deadline:
                    self._shed(req, now)
                else:
                    keep.append(req)
            self._queue = keep
            self.metrics.gauge("queue_depth", len(self._queue))

    def _collect_batch(self, probe: bool = False) -> list[_Request]:
        """Head-of-line dynamic batching: shed expired heads, take the first
        live request, then coalesce compatible requests for up to the
        batching window. ``probe`` (half-open breaker) caps the batch at one
        request — risk the minimum while testing recovery."""
        cfg = self.config
        max_size = 1 if probe else cfg.max_batch_size
        with self._wake:
            first: Optional[_Request] = None
            while self._queue:
                now = self._clock()
                req = self._queue.popleft()
                level = self._degrade_level(len(self._queue) + 1)
                if req.deadline is not None and now + self._estimated_batch_s() > req.deadline:
                    self._shed(req, now)
                    continue
                self._clamp_budget(req, level)
                first = req
                break
            if first is None:
                self.metrics.gauge("queue_depth", len(self._queue))
                return []
            batch = [first]
            key = first.group_key()
            window_end = self._clock() + cfg.batch_window_s
            while len(batch) < max_size and not self._draining:
                if self._queue:
                    now = self._clock()
                    head = self._queue[0]
                    if head.deadline is not None and now + self._estimated_batch_s() > head.deadline:
                        self._shed(self._queue.popleft(), now)
                        continue
                    self._clamp_budget(head, self._degrade_level(len(self._queue)))
                    if head.group_key() != key:
                        break  # incompatible head stays for the next batch
                    batch.append(self._queue.popleft())
                    continue
                remaining = window_end - self._clock()
                if remaining <= 0:
                    break
                self._wake.wait(timeout=remaining)
            self.metrics.gauge("queue_depth", len(self._queue))
        self.metrics.bump("degraded", sum(1 for r in batch if r.degraded))
        return batch

    # -------------------------------------------------------- batch execution
    def _bucket_rows(self, n: int) -> int:
        if not self.config.batch_bucket:
            return n
        b = 1
        while b < n:
            b *= 2
        return min(b, max(self.config.max_batch_size, n))

    def _default_generate(self, model, ids, **kwargs):
        from .inference import generate

        return generate(model, ids, **kwargs)

    def _run_batch(self, batch: list[_Request]) -> np.ndarray:
        cfg = self.config
        first = batch[0]
        rows = np.stack([r.input_ids for r in batch])
        target = self._bucket_rows(len(batch))
        if target > len(batch):  # pad rows so the LRU sees pow-2 batch shapes
            pad = np.repeat(rows[:1], target - len(batch), axis=0)
            rows = np.concatenate([rows, pad], axis=0)
        total = rows.shape[1] + first.effective_max_new_tokens
        pad_to = -(-total // cfg.pad_total_multiple) * cfg.pad_total_multiple
        kv_kwargs = {}
        if cfg.kv_cache != "dense":  # dense is the default inside generate()
            kv_kwargs = {
                "kv_backend": cfg.kv_cache,
                "kv_block_size": cfg.engine_block_size,
            }
        out = self._generate_fn(
            self.model,
            rows,
            max_new_tokens=first.effective_max_new_tokens,
            temperature=first.temperature,
            seed=first.seed,
            pad_to=pad_to,
            top_k=first.top_k,
            top_p=first.top_p,
            eos_token_id=first.eos_token_id,
            pad_token_id=first.pad_token_id,
            **kv_kwargs,
        )
        # realize on host here — a transfer error is a batch failure, not a
        # mystery the client trips over later
        return np.asarray(out)[: len(batch)]  # graft: sync-ok — batch boundary

    def _execute(self, batch: list[_Request]) -> None:
        cfg = self.config
        attempt = 0
        while True:
            try:
                # clock first: an armed serving_before_batch sleep (the
                # obs-bench drift chaos) must land inside the measured
                # window, exactly like a genuinely slow batch would
                t0 = self._clock()
                fault_point("serving_before_batch", replica=self.replica_id)
                with tracing.span(
                    "serving.batch",
                    trace_id=batch[0].trace_id,
                    batch_size=len(batch),
                    attempt=attempt,
                ):
                    out = self._run_batch(batch)
                dt = self._clock() - t0
                fault_point("serving_after_batch", replica=self.replica_id)
            except BaseException as exc:  # noqa: BLE001 — classified below
                if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                    # the worker is about to die — the in-flight batch must
                    # not leave clients blocked on unresolved futures
                    self._fail_batch(
                        batch, exc, "worker interrupted mid-batch",
                        err_cls=ReplicaDeadError,
                    )
                    raise
                attempt += 1
                self.metrics.bump("batch_failures")
                opened = self._breaker.record_failure()
                if opened:
                    self.metrics.bump("breaker_opens")
                    logger.warning(
                        "circuit breaker OPEN after %d consecutive batch "
                        "failures (last: %s)",
                        cfg.breaker_threshold, exc,
                    )
                if attempt > cfg.max_retries or self._draining:
                    self._fail_batch(
                        batch, exc,
                        f"batch failed permanently after {attempt} attempt(s)",
                    )
                    return
                self.metrics.bump("retries")
                backoff = min(
                    cfg.retry_backoff_s * (2 ** (attempt - 1)),
                    cfg.retry_backoff_max_s,
                )
                backoff *= 1.0 + cfg.retry_jitter * self._rng.random()
                logger.warning(
                    "batch attempt %d/%d failed (%s: %s); retrying in %.3fs",
                    attempt, cfg.max_retries + 1, type(exc).__name__, exc, backoff,
                )
                # interruptible sleep: a drain request must not wait out the
                # whole backoff ladder
                with self._wake:
                    self._wake.wait(timeout=backoff)
                continue
            break
        # success epilogue — guarded: the batch has already executed, so any
        # failure past this point (an armed ``serving_before_reply`` fault,
        # a pathological tracker/metrics error) must fail THIS batch's
        # outstanding futures rather than escape with them unresolved
        try:
            self._breaker.record_success()
            self.metrics.bump("batches")
            self._batch_time_ewma = (
                dt if self._batch_time_ewma == 0.0
                else 0.8 * self._batch_time_ewma + 0.2 * dt
            )
            # static batches have no baseline program; the observatory
            # still tracks them (measured-only row) — dt is the wall time
            # this loop already measured, no new sync point
            perfwatch.get_watch().record("serving.static/batch", dt)
            fault_point("serving_before_reply", replica=self.replica_id)
            now = self._clock()
            for i, req in enumerate(batch):
                if req.deadline is not None and now > req.deadline:
                    late = self._resolve(
                        req.future,
                        exception=RequestDeadlineExceeded(
                            f"batch completed {now - req.deadline:.3f}s past "
                            "the deadline",
                            replica_id=self.replica_id,
                        ),
                    )
                    if late:
                        self.metrics.bump("completed_late")
                    continue
                latency = now - req.submitted_at
                delivered = self._resolve(
                    req.future,
                    result=ServingResult(
                        tokens=out[i],
                        latency_s=latency,
                        batch_size=len(batch),
                        degraded=req.degraded,
                        ttft_s=latency,  # whole batch materializes at once
                        replica_id=self.replica_id,
                        queue_wait_s=max(0.0, latency - dt),
                        decode_steps=req.effective_max_new_tokens,
                    ),
                )
                if delivered:
                    self.metrics.bump("completed")
                    self.metrics.latency.add(latency)
                    self.metrics.ttft.add(latency)  # batch materializes at once
                    self.metrics.queue_wait.add(max(0.0, latency - dt))
        except BaseException as exc:  # noqa: BLE001 — never strand a batch
            self._fail_batch(batch, exc, "batch executed but the reply failed")
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            logger.exception(
                "serving reply epilogue failed; the batch's outstanding "
                "futures were failed with BatchExecutionError"
            )

    def _fail_batch(
        self, batch: list[_Request], cause: BaseException, reason: str,
        err_cls: type = BatchExecutionError,
    ) -> None:
        err = err_cls(
            f"{reason}: {type(cause).__name__}: {cause}",
            replica_id=self.replica_id,
        )
        err.__cause__ = cause
        for req in batch:
            self._resolve(req.future, exception=err)

    def _reject_queued(self) -> None:
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
            self.metrics.gauge("queue_depth", 0)
        for req in pending:
            rejected = self._resolve(
                req.future,
                exception=ServerDrainingError(
                    "server drained before this request was batched — "
                    "resubmit to another replica",
                    replica_id=self.replica_id,
                    retry_after_s=0.0,
                ),
            )
            if rejected:
                self.metrics.bump("rejected_draining")

    # --------------------------------------------------------------- metrics
    def _flush_due(self) -> bool:
        return bool(self.trackers) and self.metrics.registry.due(
            self.config.metrics_interval_s
        )

    def _flush_metrics(self, force: bool = False) -> None:
        """Periodic tracker flush, deduped through the registry (the cadence
        bookkeeping and ``log_batch`` bridge live in
        :meth:`MetricsRegistry.flush` — ``FleetMetrics`` rides the same
        path). Always called with the server lock released (G104)."""
        if not self.trackers:
            return
        reg = self.metrics.registry
        if force or reg.due(self.config.metrics_interval_s):
            self.metrics.gauge("breaker_state", self._breaker.state())
            reg.flush(self.trackers)

    def log_metrics(self, step: Optional[int] = None, trackers: Optional[Sequence] = None):
        """Push one metrics snapshot through ``GeneralTracker.log_batch``
        (explicit sibling of the periodic ``metrics_interval_s`` flow).
        Returns the snapshot dict."""
        self.metrics.gauge("breaker_state", self._breaker.state())
        snapshot = self.metrics.snapshot()
        for tracker in trackers if trackers is not None else self.trackers:
            tracker.log_batch([(snapshot, step, {})])
        return snapshot


# ----------------------------------------------------------------- drain hook
def install_drain_handler(
    server: InferenceServer,
    signals: tuple = (signal.SIGTERM, signal.SIGINT),
    exit_code: int = PREEMPTION_EXIT_CODE,
) -> bool:
    """SIGTERM → graceful drain → ``sys.exit(143)`` — the serving twin of
    :func:`~accelerate_tpu.utils.fault.install_preemption_handler` (which
    handles the *training* side: emergency checkpoint). Admission stops,
    the in-flight batch finishes and replies, queued requests get a
    retriable :class:`~accelerate_tpu.utils.fault.ServerDrainingError`.

    Only installable from the main thread (Python restriction); returns
    False elsewhere. A second signal during the drain is absorbed."""
    if threading.current_thread() is not threading.main_thread():
        return False
    state = {"draining": False}

    def _handler(signum, frame):
        if state["draining"]:
            return
        state["draining"] = True
        logger.warning(
            "received signal %d — draining inference server before exit", signum
        )
        try:
            from .utils.fault import _record_preemption

            _record_preemption(signum)
            server.close(drain=True)
        finally:
            sys.exit(exit_code)

    for sig in signals:
        signal.signal(sig, _handler)
    return True
