"""Plugin/config dataclasses and kwargs handlers.

TPU-native analogue of the reference's ``utils/dataclasses.py`` (3,228 LoC).
The reference needs one plugin per external engine (DeepSpeedPlugin,
FullyShardedDataParallelPlugin, MegatronLMPlugin, ...); under GSPMD those
collapse into :class:`accelerate_tpu.parallelism_config.ParallelismConfig`
plus the small strategy configs here. Env-var consumption mirrors the
reference's ``__post_init__`` pattern (utils/dataclasses.py:1815-1945).
"""

from __future__ import annotations

import copy
import dataclasses
import enum
import os
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Optional

from .environment import parse_flag_from_env


class KwargsHandler:
    """Base: diff against defaults → kwargs dict (reference
    utils/dataclasses.py:70-88)."""

    def to_dict(self) -> dict:
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self) -> dict:
        default = self.__class__()
        this = self.to_dict()
        return {k: v for k, v in this.items() if getattr(default, k, None) != v}


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Gradient accumulation settings (reference utils/dataclasses.py
    ``GradientAccumulationPlugin``).

    ``sync_with_dataloader``: force a sync step when the dataloader ends even
    if mid-accumulation-window (reference GradientState semantics).
    """

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False

    def __post_init__(self):
        if self.num_steps < 1:
            raise ValueError(f"num_steps must be >= 1, got {self.num_steps}")


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """DDP tuning knobs (reference DistributedDataParallelKwargs +
    DDPCommunicationHookType, utils/dataclasses.py:136-242).

    Most reference fields (bucket_cap_mb, static_graph, find_unused_parameters)
    tune torch DDP's bucketed autograd hooks and have no GSPMD meaning — XLA
    schedules gradient collectives itself. The surviving semantics are the
    *communication hooks*: compressing gradient reduction to bf16/fp16
    (``comm_hook``), realized by casting gradients before accumulation/
    reduction in the train step, and PowerSGD low-rank compression
    (``comm_hook="powersgd"`` + ``powersgd_rank``) for the slow
    ``dp_replicate`` (DCN) axis — the reference's
    DDPCommunicationHookType.POWER_SGD, realized natively in
    ops/powersgd.py as a shard_map over the replicate axis whose
    cross-replica reductions move only the rank-r factors, with per-replica
    error feedback."""

    comm_hook: str = "no"  # "no" | "bf16" | "fp16" | "powersgd"
    comm_wrapper: str = "no"  # parity placeholder (bf16-wrapping a low-rank
    # factor reduction saves little; kept for surface parity)
    powersgd_rank: int = 4

    def __post_init__(self):
        if self.comm_hook not in ("no", "bf16", "fp16", "powersgd"):
            raise ValueError(
                f"comm_hook must be no|bf16|fp16|powersgd, got {self.comm_hook}"
            )
        if self.comm_wrapper != "no":
            raise ValueError(
                "comm_wrapper variants are torch-DDP bucket machinery with "
                f"no GSPMD analogue; got {self.comm_wrapper!r}"
            )
        if self.powersgd_rank < 1:
            raise ValueError(f"powersgd_rank must be >= 1, got {self.powersgd_rank}")

    @property
    def gradient_dtype(self):
        import jax.numpy as jnp

        return {
            "no": None, "powersgd": None,
            "bf16": jnp.bfloat16, "fp16": jnp.float16,
        }[self.comm_hook]


@dataclass
class AutocastKwargs(KwargsHandler):
    """Mixed-precision autocast knobs (reference utils/dataclasses.py:
    ``AutocastKwargs``): enabled flag + cache control is torch-specific, our
    knob is the compute dtype override."""

    enabled: bool = True
    cache_enabled: bool = True  # accepted for parity; XLA caches compiled fns


@dataclass
class GradScalerKwargs(KwargsHandler):
    """Dynamic loss-scaling config for fp16 (reference GradScalerKwargs /
    torch GradScaler defaults)."""

    init_scale: float = 2.0**16
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """Process bootstrap kwargs (reference InitProcessGroupKwargs — timeout
    for jax.distributed.initialize)."""

    backend: Optional[str] = "xla"
    init_method: Optional[str] = None
    timeout: Optional[timedelta] = None


class PrecisionType(str, enum.Enum):
    NO = "no"
    BF16 = "bf16"
    FP16 = "fp16"
    FP8 = "fp8"

    @classmethod
    def list(cls):
        return [e.value for e in cls]


@dataclass
class MixedPrecisionPolicy(KwargsHandler):
    """Three-dtype policy (param/compute/output), the jmp-style TPU-native
    replacement for torch autocast (reference wraps torch.autocast,
    accelerator.py:561-612)."""

    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    output_dtype: str = "float32"

    @classmethod
    def from_mixed_precision(cls, mixed_precision: str) -> "MixedPrecisionPolicy":
        if mixed_precision == "bf16":
            return cls(param_dtype="float32", compute_dtype="bfloat16", output_dtype="float32")
        if mixed_precision == "fp16":
            return cls(param_dtype="float32", compute_dtype="float16", output_dtype="float32")
        if mixed_precision == "fp8":
            # fp8 matmul inputs; accumulation still bf16/f32 (see ops/fp8.py)
            return cls(param_dtype="float32", compute_dtype="bfloat16", output_dtype="float32")
        return cls()

    def cast_to_compute(self, tree):
        import jax.numpy as jnp
        from ..ops.operations import recursively_apply, is_tensor

        dtype = jnp.dtype(self.compute_dtype)

        def cast(t):
            if hasattr(t, "dtype") and jnp.issubdtype(t.dtype, jnp.floating):
                return t.astype(dtype)
            return t

        return recursively_apply(cast, tree)

    def cast_to_output(self, tree):
        import jax.numpy as jnp
        from ..ops.operations import recursively_apply

        dtype = jnp.dtype(self.output_dtype)

        def cast(t):
            if hasattr(t, "dtype") and jnp.issubdtype(t.dtype, jnp.floating):
                return t.astype(dtype)
            return t

        return recursively_apply(cast, tree)


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """Dataloader behavior knobs (reference utils/dataclasses.py
    ``DataLoaderConfiguration``)."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    data_seed: Optional[int] = None
    non_blocking: bool = True  # parity; JAX transfers are async by default
    use_stateful_dataloader: bool = True


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/log directory layout (reference utils/dataclasses.py
    ``ProjectConfiguration``)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False
    # Retention pin: every checkpoint whose index is a multiple of this is
    # exempt from total_limit GC (keep-every-K milestones for post-hoc evals
    # while total_limit bounds the rolling recency window).
    checkpoint_keep_every: Optional[int] = None

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir
        if self.checkpoint_keep_every is not None and self.checkpoint_keep_every <= 0:
            raise ValueError("checkpoint_keep_every must be a positive integer")


@dataclass
class TrainingHealthConfig(KwargsHandler):
    """Policy for ``Accelerator.check_step_health`` — what to do when a step
    produces a non-finite loss (or gradients, with ``check_grads=True``):

    * ``"raise"`` (default) — fail fast with :class:`TrainingHealthError`;
    * ``"skip"`` — drop the step (zero the accumulated grads) and continue;
    * ``"restore"`` — reload the last committed checkpoint and continue.

    ``max_bad_steps`` bounds how many *consecutive* unhealthy steps the
    skip/restore policies tolerate before raising anyway — a persistent
    divergence should stop the job, not loop forever restoring.

    ``sync`` picks between per-step exactness and a full dispatch
    pipeline (docs/fault_tolerance.md "Telemetry cost"):

    * ``sync=True`` (default) — the verdict for step S is read back and
      applied inside step S's ``check_step_health`` call. Exact, but a
      host sync point per call (still only ONE fused scalar transfer —
      the finiteness of the loss and every grad leaf is tree-reduced on
      device by ``telemetry.health_summary``).
    * ``sync=False`` — deferred-readback ring: each call enqueues this
      step's device scalars and only blocks on the value from
      ``readback_depth`` steps ago, so the host never flushes the
      dispatch pipeline it just filled. Policies apply with
      ``readback_depth``-step latency; ``Accelerator.health_drain()``
      flushes pending verdicts exactly (called by ``end_training``)."""

    nonfinite_policy: str = "raise"  # "raise" | "skip" | "restore"
    check_grads: bool = False
    max_bad_steps: int = 10
    sync: bool = True
    readback_depth: int = 2

    def __post_init__(self):
        if self.nonfinite_policy not in ("raise", "skip", "restore"):
            raise ValueError(
                f"nonfinite_policy must be raise|skip|restore, got "
                f"{self.nonfinite_policy!r}"
            )
        if self.max_bad_steps <= 0:
            raise ValueError("max_bad_steps must be a positive integer")
        if self.readback_depth < 1:
            raise ValueError("readback_depth must be a positive integer")


@dataclass
class ReplicationConfig(KwargsHandler):
    """Checkpoint replication policy for the elastic recovery subsystem
    (``accelerate_tpu.elastic``; docs/fault_tolerance.md "Replication &
    elastic resume").

    After every atomic commit the main process hands the committed
    checkpoint to a bounded background replicator that mirrors it —
    manifest-verified, retried with exponential backoff — under ``target``
    (durable storage that survives host loss: NFS, PD, a bucket mount).
    On restore, a host whose local tree is missing or fails checksum
    verification falls back to a replica, proving integrity against the
    replica's own manifest before copying it back.

    * ``target`` — root directory replicas are mirrored under. ``copies``
      independent copies live at ``target/r0/…``, ``target/r1/…``.
    * ``copies`` — how many mirror copies to maintain per checkpoint.
    * ``async_replicate`` — mirror on a background thread (never blocks the
      step loop; drained by ``end_training``/preemption/atexit like async
      saves). ``False`` mirrors synchronously inside ``save_state`` and
      raises mirror failures inline — deterministic, for tests and final
      checkpoints.
    * ``max_retries`` / ``retry_backoff_s`` — per-mirror retry budget and
      initial backoff (doubles per attempt).
    * ``verify`` — integrity level a freshly staged replica must pass
      before its commit rename: ``"size"`` or ``"checksum"``.
    * ``keep`` — replica retention: keep only the newest ``keep`` committed
      replicas per copy dir (``None`` keeps everything).
    """

    target: str = ""
    copies: int = 1
    async_replicate: bool = True
    max_retries: int = 3
    retry_backoff_s: float = 0.25
    verify: str = "checksum"
    keep: Optional[int] = None

    def __post_init__(self):
        if not self.target:
            raise ValueError("ReplicationConfig.target must be a non-empty path")
        if self.copies < 1:
            raise ValueError("copies must be a positive integer")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.retry_backoff_s < 0:
            raise ValueError("retry_backoff_s must be >= 0")
        if self.verify not in ("size", "checksum"):
            raise ValueError(
                f"verify must be size|checksum, got {self.verify!r}"
            )
        if self.keep is not None and self.keep < 1:
            raise ValueError("keep must be None or a positive integer")


@dataclass
class TracingConfig(KwargsHandler):
    """Policy knobs for the span tracer + flight recorder
    (:mod:`accelerate_tpu.tracing`, docs/observability.md).

    * ``enabled`` — master switch. The default tracer reads the
      ``ACCELERATE_TRACING`` env var (anything but ``0``/``false``/
      ``off``/``no`` keeps the always-on recorder); a config passed to
      ``tracing.configure`` wins outright. Disabled spans cost one
      attribute check (no allocation, no clock read).
    * ``ring_capacity`` — spans retained per thread ring; overflow drops
      the OLDEST span and counts it (``dropped_spans``).
    * ``retain_s`` — flight-recorder window: a dump serializes only spans
      that ended within the last ``retain_s`` seconds.
    * ``dump_dir``/``max_dumps`` — where auto-dumps land and how many a
      process may write (a crash loop must not fill the disk).
    * ``dump_on_failure`` — auto-dump on typed failures (worker death,
      ``FailoverExhaustedError``, checkpoint rollback). SIGUSR1 dumps are
      installed separately via ``tracing.install_signal_handlers``.
    """

    enabled: bool = True
    ring_capacity: int = 2048
    retain_s: float = 30.0
    dump_dir: str = "runs"
    max_dumps: int = 8
    dump_on_failure: bool = True

    def __post_init__(self):
        if self.ring_capacity < 16:
            raise ValueError(
                f"ring_capacity must be >= 16, got {self.ring_capacity}"
            )
        if self.retain_s <= 0:
            raise ValueError(f"retain_s must be > 0, got {self.retain_s}")
        if self.max_dumps < 0:
            raise ValueError(f"max_dumps must be >= 0, got {self.max_dumps}")


@dataclass
class ObservabilityConfig(KwargsHandler):
    """Policy knobs for the runtime performance observatory
    (:mod:`accelerate_tpu.perfwatch`, docs/observability.md).

    * ``enabled`` — master switch for program timers. The default watch
      reads the ``ACCELERATE_PERFWATCH`` env var (``0``/``false``/
      ``off``/``no`` disables — perfwatch is **on by default** because a
      disabled record is one attribute check); a config passed to
      ``perfwatch.configure`` wins outright.
    * ``ewma_alpha`` — weight of the newest sample in the per-program
      EWMA gauge (``perf/<program>/ewma_s``).
    * ``window`` — ``LatencyReservoir`` size per program (percentiles
      are computed over the last ``window`` samples).
    * ``baseline_path`` — where the committed per-program roofline
      predictions live (``runs/perf_baseline.json``). Missing file =
      measured-only mode, never an error.
    * ``drift_enabled`` — arm the drift sentinel. Off by default: the
      committed predictions model v5p hardware, so comparing them
      against CPU-simulator wall times would page someone every run.
      Turn on where measured and modeled hardware actually match.
    * ``drift_tolerance`` — override of the baseline file's committed
      ``tolerance`` band (``None`` = use the file's).
    * ``drift_min_samples`` — a program's median is only compared once
      this many samples landed (cold-start compile steps would
      otherwise trip the band instantly).
    * ``drift_consecutive`` — evaluations in a row the median must sit
      outside the band before the sentinel fires ("sustained drift",
      not one noisy window).
    * ``drift_interval_s`` — minimum seconds between sentinel
      evaluations (driven opportunistically from the record path — no
      dedicated thread).
    * ``exporter_port`` — serve ``/metrics`` (Prometheus text) and
      ``/snapshot.json`` on this port. 0 (default) = no HTTP thread at
      all; the ``ACCELERATE_METRICS_PORT`` env var seeds the default
      config's port.
    * ``exporter_host`` — bind address for the exporter (loopback by
      default; an operator who wants a fleet-wide scrape binds the
      router's exporter, not every replica's).
    """

    enabled: bool = True
    ewma_alpha: float = 0.2
    window: int = 512
    baseline_path: str = os.path.join("runs", "perf_baseline.json")
    drift_enabled: bool = False
    drift_tolerance: Optional[float] = None
    drift_min_samples: int = 8
    drift_consecutive: int = 2
    drift_interval_s: float = 1.0
    exporter_port: int = 0
    exporter_host: str = "127.0.0.1"

    def __post_init__(self):
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError(
                f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}"
            )
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.drift_tolerance is not None and self.drift_tolerance <= 0:
            raise ValueError(
                f"drift_tolerance must be > 0, got {self.drift_tolerance}"
            )
        if self.drift_min_samples < 1:
            raise ValueError(
                f"drift_min_samples must be >= 1, got {self.drift_min_samples}"
            )
        if self.drift_consecutive < 1:
            raise ValueError(
                f"drift_consecutive must be >= 1, got {self.drift_consecutive}"
            )
        if self.drift_interval_s < 0:
            raise ValueError(
                f"drift_interval_s must be >= 0, got {self.drift_interval_s}"
            )
        if not 0 <= self.exporter_port <= 65535:
            raise ValueError(
                f"exporter_port must be in [0, 65535], got {self.exporter_port}"
            )


@dataclass
class ServingConfig(KwargsHandler):
    """Policy knobs for :class:`accelerate_tpu.serving.InferenceServer`
    (docs/serving.md). Robustness-first defaults: bounded everything.

    Admission / batching:

    * ``max_queue`` — bounded admission queue; a full queue rejects with
      :class:`~accelerate_tpu.utils.fault.ServerOverloaded` (backpressure,
      never unbounded memory).
    * ``max_batch_size`` / ``batch_window_s`` — dynamic batching: the worker
      takes the head request and coalesces compatible requests (same prompt
      length / token budget / sampling shape) for up to ``batch_window_s``.
    * ``batch_bucket`` — round the executed batch up to the next power of
      two (rows padded) so the compiled-program LRU sees O(log
      max_batch_size) batch shapes, not one per occupancy.
    * ``pad_total_multiple`` — bucket ``prompt+new`` total length up to this
      multiple (the ``pad_to`` knob of :func:`~accelerate_tpu.inference
      .generate`), bounding per-length recompiles.

    Deadlines: ``default_deadline_s`` applies when ``submit`` passes none
    (``None`` = no deadline). Enforced at dequeue (a request that cannot
    finish in time is shed instead of wasting a batch slot) and again at
    completion.

    Retry / circuit breaker: failed batches retry up to ``max_retries``
    with exponential backoff (``retry_backoff_s`` base, doubled per
    attempt, capped at ``retry_backoff_max_s``, ±``retry_jitter``
    fractional jitter). ``breaker_threshold`` consecutive failed attempts
    open the breaker: submissions fail fast with
    :class:`~accelerate_tpu.utils.fault.CircuitOpenError` until
    ``breaker_reset_s`` passes, then ONE half-open probe batch decides
    between closing and re-opening.

    Degradation ladder (before shedding): above ``degrade_queue_fraction``
    queue occupancy, per-request token budgets are clamped to
    ``degraded_max_new_tokens``; above ``degrade_hard_fraction`` they are
    clamped to half that. Cheaper batches drain the queue faster than
    rejecting ever could.

    Drain: ``drain_timeout_s`` bounds how long ``close(drain=True)`` (and
    the SIGTERM handler) waits for in-flight batches.

    ``metrics_interval_s`` — when set (and trackers are attached), the
    worker pushes a metrics snapshot through ``GeneralTracker.log_batch``
    at this cadence.

    Scheduling mode: ``mode="static"`` (default) keeps admission-time
    batching of whole ``generate()`` calls; ``mode="continuous"`` runs the
    slot-based continuous-batching engine
    (:class:`accelerate_tpu.engine.ContinuousBatchingEngine`) — the worker
    becomes an iteration-level scheduler admitting requests into
    ``engine_slots`` KV-arena slots of ``engine_max_len`` positions each.
    Prompts must fit ``engine_prompt_bucket`` (default ``engine_max_len //
    2``) and ``prompt + max_new_tokens <= engine_max_len``;
    ``engine_readback_lag`` defers done-mask readback that many device
    programs (0 = synchronous, deterministic scheduling for tests). In
    continuous mode ``max_batch_size``/``batch_window_s``/``batch_bucket``/
    ``pad_total_multiple`` are inert (no admission-time batches exist);
    everything else — deadlines, backpressure, retry/breaker, degradation
    (clamping the per-slot budget, not the batch), drain — applies
    unchanged.

    KV cache backend (docs/serving.md "Paged KV & prefix caching"):
    ``kv_cache`` selects how KV is stored — ``"dense"`` (one
    ``engine_max_len`` row per slot, today's arena), ``"paged"`` (shared
    block pool + per-slot block tables + copy-on-write prefix caching;
    admission is gated on free *blocks* so short requests stop paying long
    requests' worst-case reservation), or ``"paged_int8"`` (paged with an
    int8 pool + per-block scales, ~4x less KV HBM at a bounded,
    deterministic accuracy cost). ``engine_block_size`` positions per block
    (must divide ``engine_max_len``); ``engine_pool_blocks`` sizes the pool
    (``None`` = full provisioning: ``engine_slots * engine_max_len /
    engine_block_size`` + the reserved null block — same token capacity as
    dense; set it SMALLER to oversubscribe slots at fixed HBM). In static
    mode ``kv_cache`` selects :func:`~accelerate_tpu.inference.generate`'s
    ``kv_backend`` so both paths share one KV story.

    ``attention_impl`` selects the decode/verify attention implementation
    over a paged pool — ``"reference"`` (the XLA gather-then-attend op,
    default) or ``"pallas"`` (the fused TPU flash-decode kernels in
    ``ops/paged_decode.py``: the block table is walked inside the kernel so
    HBM traffic scales with LIVE blocks, int8 dequantizes in-register, and
    sampling runs as a fused epilogue kernel). Requires a paged
    ``kv_cache``; on CPU the kernels run under ``interpret=True`` with
    exact (f32) / bounded (int8, 4.0e-3·amax) parity vs the reference op.

    Speculative decoding (docs/serving.md "Speculative decoding"):
    ``speculative`` — ``None`` (off, default) or ``"ngram"``: continuous
    mode drafts up to ``spec_draft_len`` tokens per live slot from a
    host-side prompt-lookup n-gram match over the slot's own history (no
    second model) and verifies the whole window in ONE fused
    ``verify_step`` program, committing only the accepted prefix's KV.
    Greedy outputs are bitwise identical to plain decode; sampled outputs
    keep the engine's seeded-reproducibility contract. The worker drops
    the draft limit under queue pressure (cheapest rung of the
    degradation ladder) and restores it when pressure subsides; the
    engine itself falls back to plain ``decode_step`` for slots whose
    acceptance EWMA collapses. Requires ``mode="continuous"``.

    Long-context serving (docs/serving.md "Long-context serving"):
    ``engine_prefill_chunk`` — when set, prompts longer than
    ``engine_prompt_bucket`` are admitted anyway and prefilled in chunks
    of this many positions, ONE chunk per scheduler tick interleaved
    with other slots' decode steps (Sarathi-style stall-free batching);
    greedy f32 output is bitwise identical to a single-shot prefill.
    ``kv_host_tier_bytes`` — capacity of a pinned host-RAM tier below
    the paged pool's zero-ref cached-LRU: evicted prefix blocks spill
    there (payload + scales on a background thread) instead of dying,
    and a later request with the same prefix restores them with one
    device scatter instead of recomputing the prompt forward. Requires a
    paged ``kv_cache``. ``kv_prefetch`` — start the host-to-device copy
    of a spilled prefix at ``submit()`` time (async, submitter's thread)
    so the payload is already in flight when the request is admitted.
    """

    mode: str = "static"
    engine_slots: int = 8
    engine_max_len: int = 256
    engine_prompt_bucket: Optional[int] = None
    engine_readback_lag: int = 2
    kv_cache: str = "dense"
    engine_block_size: int = 16
    engine_pool_blocks: Optional[int] = None
    attention_impl: str = "reference"
    speculative: Optional[str] = None
    spec_draft_len: int = 4
    engine_prefill_chunk: Optional[int] = None
    kv_host_tier_bytes: int = 0
    kv_prefetch: bool = True
    max_queue: int = 256
    max_batch_size: int = 8
    batch_window_s: float = 0.002
    batch_bucket: bool = True
    pad_total_multiple: int = 64
    default_max_new_tokens: int = 32
    default_deadline_s: Optional[float] = None
    max_retries: int = 2
    retry_backoff_s: float = 0.05
    retry_backoff_max_s: float = 2.0
    retry_jitter: float = 0.25
    breaker_threshold: int = 5
    breaker_reset_s: float = 5.0
    degrade_queue_fraction: float = 0.5
    degrade_hard_fraction: float = 0.8
    degraded_max_new_tokens: int = 16
    drain_timeout_s: float = 30.0
    metrics_interval_s: Optional[float] = None

    def __post_init__(self):
        if self.mode not in ("static", "continuous"):
            raise ValueError(
                f"mode must be 'static' or 'continuous', got {self.mode!r}"
            )
        if self.engine_slots < 1:
            raise ValueError(f"engine_slots must be >= 1, got {self.engine_slots}")
        if self.engine_max_len < 2:
            raise ValueError(
                f"engine_max_len must be >= 2, got {self.engine_max_len}"
            )
        if self.engine_prompt_bucket is not None and not (
            1 <= self.engine_prompt_bucket <= self.engine_max_len - 1
        ):
            raise ValueError(
                "engine_prompt_bucket must be in [1, engine_max_len-1], got "
                f"{self.engine_prompt_bucket} (engine_max_len="
                f"{self.engine_max_len})"
            )
        if self.engine_readback_lag < 0:
            raise ValueError(
                f"engine_readback_lag must be >= 0, got {self.engine_readback_lag}"
            )
        if self.kv_cache not in ("dense", "paged", "paged_int8"):
            raise ValueError(
                "kv_cache must be 'dense', 'paged' or 'paged_int8', got "
                f"{self.kv_cache!r}"
            )
        if self.engine_block_size < 1:
            raise ValueError(
                f"engine_block_size must be >= 1, got {self.engine_block_size}"
            )
        if (
            self.kv_cache != "dense"
            and self.engine_max_len % self.engine_block_size != 0
        ):
            raise ValueError(
                f"engine_max_len ({self.engine_max_len}) must be a multiple "
                f"of engine_block_size ({self.engine_block_size}) so a block "
                "table row covers the arena length exactly"
            )
        if self.attention_impl not in ("reference", "pallas"):
            raise ValueError(
                "attention_impl must be 'reference' or 'pallas', got "
                f"{self.attention_impl!r}"
            )
        if self.attention_impl == "pallas" and self.kv_cache not in (
            "paged", "paged_int8"
        ):
            raise ValueError(
                "attention_impl='pallas' requires a paged KV cache "
                "(kv_cache='paged' or 'paged_int8'); the flash-decode kernel "
                "walks block tables, which the dense arena does not have"
            )
        if self.attention_impl == "pallas" and self.mode != "continuous":
            raise ValueError(
                "attention_impl='pallas' requires mode='continuous' (the "
                "static generate() path has no paged decode hot loop to fuse)"
            )
        if self.engine_pool_blocks is not None and self.engine_pool_blocks < 2:
            raise ValueError(
                "engine_pool_blocks must be None (full provisioning) or >= 2 "
                f"(1 block is the reserved null block), got "
                f"{self.engine_pool_blocks}"
            )
        if self.speculative not in (None, "ngram"):
            raise ValueError(
                f"speculative must be None or 'ngram', got {self.speculative!r}"
            )
        if self.speculative is not None and self.mode != "continuous":
            raise ValueError(
                "speculative decoding requires mode='continuous' (the static "
                "path has no slot engine to verify drafts in)"
            )
        if self.speculative is not None and self.spec_draft_len < 1:
            raise ValueError(
                f"spec_draft_len must be >= 1 when speculative is enabled, "
                f"got {self.spec_draft_len}"
            )
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.max_batch_size < 1:
            raise ValueError(
                f"max_batch_size must be >= 1, got {self.max_batch_size}"
            )
        if self.batch_window_s < 0 or self.batch_window_s > 10:
            raise ValueError(
                f"batch_window_s must be in [0, 10], got {self.batch_window_s}"
            )
        if self.pad_total_multiple < 1:
            raise ValueError(
                f"pad_total_multiple must be >= 1, got {self.pad_total_multiple}"
            )
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.retry_backoff_s < 0 or self.retry_backoff_max_s < self.retry_backoff_s:
            raise ValueError(
                "retry backoff must satisfy 0 <= retry_backoff_s <= "
                f"retry_backoff_max_s, got {self.retry_backoff_s}/"
                f"{self.retry_backoff_max_s}"
            )
        if not 0 <= self.retry_jitter <= 1:
            raise ValueError(f"retry_jitter must be in [0, 1], got {self.retry_jitter}")
        if self.breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {self.breaker_threshold}"
            )
        if self.breaker_reset_s <= 0:
            raise ValueError(
                f"breaker_reset_s must be > 0, got {self.breaker_reset_s}"
            )
        if not 0 < self.degrade_queue_fraction <= 1:
            raise ValueError(
                "degrade_queue_fraction must be in (0, 1], got "
                f"{self.degrade_queue_fraction}"
            )
        if not self.degrade_queue_fraction <= self.degrade_hard_fraction <= 1:
            raise ValueError(
                "degrade_hard_fraction must be in [degrade_queue_fraction, 1], "
                f"got {self.degrade_hard_fraction}"
            )
        if self.degraded_max_new_tokens < 1:
            raise ValueError(
                "degraded_max_new_tokens must be >= 1, got "
                f"{self.degraded_max_new_tokens}"
            )
        if self.engine_prefill_chunk is not None and not (
            1 <= self.engine_prefill_chunk <= self.engine_max_len - 1
        ):
            raise ValueError(
                "engine_prefill_chunk must be in [1, engine_max_len-1], got "
                f"{self.engine_prefill_chunk} (engine_max_len="
                f"{self.engine_max_len})"
            )
        if self.engine_prefill_chunk is not None and self.mode != "continuous":
            raise ValueError(
                "engine_prefill_chunk requires mode='continuous' (chunked "
                "prefill is a slot-engine scheduling feature)"
            )
        if self.kv_host_tier_bytes < 0:
            raise ValueError(
                f"kv_host_tier_bytes must be >= 0, got {self.kv_host_tier_bytes}"
            )
        if self.kv_host_tier_bytes > 0 and self.kv_cache not in (
            "paged", "paged_int8"
        ):
            raise ValueError(
                "kv_host_tier_bytes requires a paged KV cache (the host tier "
                "spills/restores pool blocks, which the dense arena does not "
                "have)"
            )


@dataclass
class FleetConfig(KwargsHandler):
    """Policy knobs for :class:`accelerate_tpu.fleet.FleetRouter`
    (docs/serving.md "Multi-replica fleet"). All failover/hedging traffic
    is bounded — a replica outage must degrade goodput, never amplify it.

    Placement: ``placement`` — ``"least_loaded"`` (default) scores each
    routable replica by outstanding work (queued + in flight, scaled by
    its batch-time EWMA when a deadline makes time matter) and takes the
    minimum; ``"round_robin"`` ignores load. Replicas that are draining,
    dead, or behind an OPEN router-side breaker are never candidates.

    Health / breakers: a prober thread samples every replica's
    :meth:`~accelerate_tpu.serving.InferenceServer.health` each
    ``probe_interval_s``; per-replica circuit breakers (same three-state
    machine as the server's own) open after ``breaker_threshold``
    consecutive replica-level failures and re-probe after
    ``breaker_reset_s``. With ``auto_respawn`` and a ``replica_factory``,
    a replica whose worker died is relaunched (supervisor-style scale-up)
    after ``respawn_backoff_s``.

    Failover: a request that fails with a *retriable* typed error
    (``retriable`` attribute — never message prose) is transparently
    resubmitted to a surviving replica, at most ``max_failovers`` times
    per request, spending one token of the fleet-wide retry budget (a
    token bucket of ``retry_budget_capacity`` refilled at
    ``retry_budget_refill_per_s``) per unplanned failover. Planned drains
    (:class:`~accelerate_tpu.utils.fault.ServerDrainingError`, i.e.
    scale-down redistribution) are exempt from the bucket — an orderly
    drain fails each queued request exactly once, so zero-drop scale-down
    never competes with outage retries for budget.

    Hedging: with ``hedge_deadline_fraction`` set, a request whose
    remaining deadline is below that fraction of its estimated completion
    time on the chosen replica is dispatched to a second replica as well
    (first result wins, the loser is cancelled); each hedge also spends a
    retry-budget token so hedging can never storm.

    Brown-out quarantine (gray failures — docs/fault_tolerance.md): every
    probe is timeout-bounded (``probe_timeout_s``) and the prober pass is
    concurrent, so one hung ``health()`` can never stall the loop or
    stale the controller's freshness stamp. A replica whose probe-latency
    EWMA crosses ``brownout_probe_ewma_s``, whose perfwatch
    measured-vs-predicted ratio (``perf/<prog>/ratio``, from its own
    snapshot) crosses ``brownout_residual_ratio``, or whose probe hangs
    outright, enters the **brown-out** state: still routable (it is not
    dead), but its placement score is multiplied by
    ``brownout_placement_penalty``, it becomes the preferred hedge
    *source* (with ``hedge_brownout``, its in-flight requests are hedged
    to a healthy replica, one retry-budget token each), and after
    ``brownout_drain_after_s`` of sustained brown-out a typed
    :class:`~accelerate_tpu.utils.fault.ReplicaBrownoutError` is filed
    into perfwatch's findings so the SLO controller drains and replaces
    it zero-drop. The state clears (hysteresis) only when the score falls
    below ``brownout_clear_fraction`` of the engage threshold.

    Prefill/decode disaggregation: ``disaggregate_prefill`` routes
    continuous-mode requests through ``prefill_workers`` dedicated worker
    threads that run the engine's prompt forward
    (:meth:`~accelerate_tpu.engine.ContinuousBatchingEngine
    .prefill_remote`) *off* the decode loop, handing the decode replica a
    precomputed KV window to scatter (``insert_prefilled``). Decode slots
    stop stalling behind compute-bound prompt forwards;
    ``ServingResult.ttft_s`` is the metric.

    Wire-capable KV transfer (``accelerate_tpu.kvtransfer``,
    docs/serving.md "Cross-host disaggregated prefill"): ``kv_transfer``
    selects a transport (``"inproc"`` — the bitwise-parity oracle, or
    ``"tcp"`` — length-prefixed sockets, the genuinely cross-host path;
    ``None`` keeps today's by-reference hand-off). The prefill worker
    then *ships* each ``RemotePrefill`` as an epoch-fenced transactional
    chunk stream: ``kv_transfer_chunk_bytes`` per CHUNK frame, each ACK
    bounded by ``kv_transfer_chunk_deadline_s``, up to
    ``kv_transfer_retries`` re-attempts with ``kv_transfer_backoff_s``
    exponential backoff, every retry spending one fleet retry-budget
    token (same bucket as failovers — a transfer storm cannot outspend an
    outage). Any terminal transfer error falls back to a local prefill
    (``fleet/prefill_fallback/transfer_failed`` or ``/stale_epoch``).

    KV-affinity placement: with ``kv_affinity`` the prober gossips each
    replica's prefix-registry digest (crc32 of its block-aligned cached
    prefixes) and ``_score`` multiplies a replica's load score by
    ``kv_affinity_weight`` when it already holds a request's prefix — the
    request lands where its KV lives. ``replicate_hot_prefixes`` > 0
    additionally copies each replica's N hottest host-tier prefix blocks
    into the other replicas' host tiers on every probe pass (0 = off).
    """

    placement: str = "least_loaded"
    probe_interval_s: float = 0.25
    breaker_threshold: int = 3
    breaker_reset_s: float = 2.0
    max_failovers: int = 3
    retry_budget_capacity: int = 64
    retry_budget_refill_per_s: float = 16.0
    hedge_deadline_fraction: Optional[float] = None
    disaggregate_prefill: bool = False
    prefill_workers: int = 2
    # wire-capable KV transfer + affinity routing (docstring section above)
    kv_transfer: Optional[str] = None
    kv_transfer_chunk_bytes: int = 65536
    kv_transfer_chunk_deadline_s: float = 2.0
    kv_transfer_retries: int = 2
    kv_transfer_backoff_s: float = 0.05
    kv_affinity: bool = True
    kv_affinity_weight: float = 0.5
    replicate_hot_prefixes: int = 0
    auto_respawn: bool = False
    respawn_backoff_s: float = 0.5
    # gray-failure / brown-out quarantine (docstring section above)
    probe_timeout_s: float = 0.5
    brownout_probe_ewma_s: float = 0.05
    brownout_residual_ratio: float = 2.0
    brownout_clear_fraction: float = 0.5
    brownout_drain_after_s: float = 5.0
    brownout_placement_penalty: float = 4.0
    hedge_brownout: bool = True
    drain_timeout_s: float = 30.0
    default_deadline_s: Optional[float] = None
    # push a fleet metrics snapshot to the router's trackers at most this
    # often (seconds; None disables) — same MetricsRegistry flush cadence
    # the serving layer uses for ServingConfig.metrics_interval_s
    metrics_interval_s: Optional[float] = None

    def __post_init__(self):
        if self.placement not in ("least_loaded", "round_robin"):
            raise ValueError(
                "placement must be 'least_loaded' or 'round_robin', got "
                f"{self.placement!r}"
            )
        if self.probe_interval_s <= 0:
            raise ValueError(
                f"probe_interval_s must be > 0, got {self.probe_interval_s}"
            )
        if self.breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {self.breaker_threshold}"
            )
        if self.breaker_reset_s <= 0:
            raise ValueError(
                f"breaker_reset_s must be > 0, got {self.breaker_reset_s}"
            )
        if self.max_failovers < 0:
            raise ValueError(
                f"max_failovers must be >= 0, got {self.max_failovers}"
            )
        if self.retry_budget_capacity < 0:
            raise ValueError(
                "retry_budget_capacity must be >= 0, got "
                f"{self.retry_budget_capacity}"
            )
        if self.retry_budget_refill_per_s < 0:
            raise ValueError(
                "retry_budget_refill_per_s must be >= 0, got "
                f"{self.retry_budget_refill_per_s}"
            )
        if self.hedge_deadline_fraction is not None and not (
            0 < self.hedge_deadline_fraction
        ):
            raise ValueError(
                "hedge_deadline_fraction must be None or > 0, got "
                f"{self.hedge_deadline_fraction}"
            )
        if self.prefill_workers < 1:
            raise ValueError(
                f"prefill_workers must be >= 1, got {self.prefill_workers}"
            )
        if self.respawn_backoff_s < 0:
            raise ValueError(
                f"respawn_backoff_s must be >= 0, got {self.respawn_backoff_s}"
            )
        if self.probe_timeout_s <= 0:
            raise ValueError(
                f"probe_timeout_s must be > 0, got {self.probe_timeout_s}"
            )
        if self.brownout_probe_ewma_s <= 0:
            raise ValueError(
                "brownout_probe_ewma_s must be > 0, got "
                f"{self.brownout_probe_ewma_s}"
            )
        if self.brownout_residual_ratio <= 1:
            raise ValueError(
                "brownout_residual_ratio must be > 1, got "
                f"{self.brownout_residual_ratio}"
            )
        if not (0 < self.brownout_clear_fraction < 1):
            raise ValueError(
                "brownout_clear_fraction must be in (0, 1), got "
                f"{self.brownout_clear_fraction}"
            )
        if self.brownout_drain_after_s < 0:
            raise ValueError(
                "brownout_drain_after_s must be >= 0, got "
                f"{self.brownout_drain_after_s}"
            )
        if self.brownout_placement_penalty < 1:
            raise ValueError(
                "brownout_placement_penalty must be >= 1, got "
                f"{self.brownout_placement_penalty}"
            )
        if self.drain_timeout_s < 0:
            raise ValueError(
                f"drain_timeout_s must be >= 0, got {self.drain_timeout_s}"
            )
        if self.kv_transfer not in (None, "inproc", "tcp"):
            raise ValueError(
                "kv_transfer must be None, 'inproc', or 'tcp', got "
                f"{self.kv_transfer!r}"
            )
        if self.kv_transfer_chunk_bytes < 1:
            raise ValueError(
                "kv_transfer_chunk_bytes must be >= 1, got "
                f"{self.kv_transfer_chunk_bytes}"
            )
        if self.kv_transfer_chunk_deadline_s <= 0:
            raise ValueError(
                "kv_transfer_chunk_deadline_s must be > 0, got "
                f"{self.kv_transfer_chunk_deadline_s}"
            )
        if self.kv_transfer_retries < 0:
            raise ValueError(
                "kv_transfer_retries must be >= 0, got "
                f"{self.kv_transfer_retries}"
            )
        if self.kv_transfer_backoff_s < 0:
            raise ValueError(
                "kv_transfer_backoff_s must be >= 0, got "
                f"{self.kv_transfer_backoff_s}"
            )
        if not (0 < self.kv_affinity_weight <= 1):
            raise ValueError(
                "kv_affinity_weight must be in (0, 1] (a score multiplier "
                f"— lower favors affinity harder), got "
                f"{self.kv_affinity_weight}"
            )
        if self.replicate_hot_prefixes < 0:
            raise ValueError(
                "replicate_hot_prefixes must be >= 0, got "
                f"{self.replicate_hot_prefixes}"
            )


@dataclass
class ControllerConfig(KwargsHandler):
    """Policy knobs for :class:`accelerate_tpu.controller.SLOController`
    (docs/control_plane.md) — the closed-loop SLO control plane over the
    fleet observatory. The design center is that the controller must be
    MORE robust than what it controls: every destabilizing failure mode
    (flapping, actuation storms, acting on stale telemetry) has a
    dedicated guard, and every guard has a knob here.

    Loop / objectives:

    * ``interval_s`` — observation-tick cadence of the control thread.
    * ``ttft_slo_s`` — the TTFT p99 objective (seconds). The controller's
      pressure signal is the worst ratio of measured/objective across the
      active signals; ``None`` disables the TTFT term.
    * ``latency_slo_s`` — optional end-to-end latency p99 objective.
    * ``target_queue_fraction`` — queue occupancy (depth / max_queue)
      the fleet should sit at; occupancy above it contributes pressure.

    Hysteresis / anti-flapping:

    * ``escalate_threshold`` / ``relax_threshold`` — the hysteresis band.
      Pressure >= ``escalate_threshold`` escalates one rung of the knob
      ladder; pressure <= ``relax_threshold`` relaxes one rung; anything
      between is the dead band and actuates NOTHING. The gap is the
      anti-flapping margin — an oscillating signal inside the band
      produces zero actuations.
    * ``knob_cooldown_s`` — minimum seconds between actuations of the
      same in-place knob (spec clamp, degradation, admission quota,
      hedging).
    * ``scale_cooldown_s`` — minimum seconds between replica-count
      changes (scale-up/-down/replace); replica moves are the most
      expensive actuation, so they get the longest cooldown.

    Actuation storm control:

    * ``actuation_budget_capacity`` / ``actuation_budget_refill_per_s``
      — a token bucket every actuation (escalate, relax, replace) must
      take a token from; an empty bucket denies the actuation. Bounds
      how fast a buggy signal can churn the fleet.

    Fail-static (stale telemetry):

    * ``stale_after_s`` — maximum age of the fleet snapshot (the
      prober's last completed pass) before telemetry counts as stale.
    * ``min_coverage`` — minimum fraction of live replicas whose health
      must be readable at a tick; below it telemetry counts as partial.
      Stale or partial ⇒ actuation freezes and exactly one typed
      :class:`~accelerate_tpu.utils.fault.ControllerStaleError` finding
      is recorded per episode.

    Replica elasticity:

    * ``min_replicas`` / ``max_replicas`` — bounds on the controller's
      replica-count actuation (scale-up requires the router to have a
      ``replica_factory``).
    * ``replace_on_drift`` — consume perfwatch
      :class:`~accelerate_tpu.utils.fault.PerfDriftError` findings as a
      control input: probe/replace the slowest replica (scale-up a fresh
      one, zero-drop drain the drifted one) instead of paging a human.
    * ``replace_drain_timeout_s`` — drain bound for the replaced
      replica (its queued work fails over to survivors either way).

    ``dry_run`` — compute decisions, emit ``fleet.control`` spans and
    ``controller/...`` metrics, but touch NOTHING. The audit mode: run
    it against production telemetry and read what it would have done.
    """

    interval_s: float = 0.5
    ttft_slo_s: Optional[float] = 1.0
    latency_slo_s: Optional[float] = None
    target_queue_fraction: float = 0.5
    escalate_threshold: float = 1.0
    relax_threshold: float = 0.6
    knob_cooldown_s: float = 2.0
    scale_cooldown_s: float = 5.0
    actuation_budget_capacity: int = 8
    actuation_budget_refill_per_s: float = 0.5
    stale_after_s: float = 2.0
    min_coverage: float = 1.0
    min_replicas: int = 1
    max_replicas: int = 8
    replace_on_drift: bool = True
    replace_drain_timeout_s: float = 5.0
    # weight on the KV-transfer-failure pressure term: the fraction of
    # this tick's remote prefills that fell back due to transfer failure
    # (fleet/prefill_fallback/transfer_failed + /stale_epoch deltas over
    # the prefills delta) times this weight joins the max() of pressure
    # terms — a failing cross-host data path escalates BEFORE queues
    # back up behind the slower local-prefill fallback. 0 disables.
    transfer_pressure_weight: float = 2.0
    dry_run: bool = False

    def __post_init__(self):
        if self.interval_s <= 0:
            raise ValueError(f"interval_s must be > 0, got {self.interval_s}")
        if self.ttft_slo_s is not None and self.ttft_slo_s <= 0:
            raise ValueError(
                f"ttft_slo_s must be None or > 0, got {self.ttft_slo_s}"
            )
        if self.latency_slo_s is not None and self.latency_slo_s <= 0:
            raise ValueError(
                f"latency_slo_s must be None or > 0, got {self.latency_slo_s}"
            )
        if not 0 < self.target_queue_fraction <= 1:
            raise ValueError(
                "target_queue_fraction must be in (0, 1], got "
                f"{self.target_queue_fraction}"
            )
        if self.relax_threshold < 0 or self.escalate_threshold <= self.relax_threshold:
            raise ValueError(
                "hysteresis band requires 0 <= relax_threshold < "
                f"escalate_threshold, got {self.relax_threshold}/"
                f"{self.escalate_threshold}"
            )
        if self.knob_cooldown_s < 0 or self.scale_cooldown_s < 0:
            raise ValueError(
                "cooldowns must be >= 0, got "
                f"{self.knob_cooldown_s}/{self.scale_cooldown_s}"
            )
        if self.actuation_budget_capacity < 1:
            raise ValueError(
                "actuation_budget_capacity must be >= 1, got "
                f"{self.actuation_budget_capacity}"
            )
        if self.actuation_budget_refill_per_s < 0:
            raise ValueError(
                "actuation_budget_refill_per_s must be >= 0, got "
                f"{self.actuation_budget_refill_per_s}"
            )
        if self.stale_after_s <= 0:
            raise ValueError(
                f"stale_after_s must be > 0, got {self.stale_after_s}"
            )
        if not 0 < self.min_coverage <= 1:
            raise ValueError(
                f"min_coverage must be in (0, 1], got {self.min_coverage}"
            )
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                "replica bounds require 1 <= min_replicas <= max_replicas, "
                f"got {self.min_replicas}/{self.max_replicas}"
            )
        if self.replace_drain_timeout_s < 0:
            raise ValueError(
                "replace_drain_timeout_s must be >= 0, got "
                f"{self.replace_drain_timeout_s}"
            )
        if self.transfer_pressure_weight < 0:
            raise ValueError(
                "transfer_pressure_weight must be >= 0, got "
                f"{self.transfer_pressure_weight}"
            )


@dataclass
class FSDPPlugin(KwargsHandler):
    """FSDP strategy knobs mapped to GSPMD equivalents
    (reference FullyShardedDataParallelPlugin, utils/dataclasses.py:1586-2191).

    Under GSPMD there is no wrapping step: parameters whose size exceeds
    ``min_weight_size`` are sharded along their largest divisible dim over the
    ``dp_shard``(×``cp``) axes; XLA inserts all-gather/reduce-scatter.
    ``reshard_after_forward`` maps to rematerialization policy: True → params
    are re-gathered in backward (XLA default under sharding); False keeps the
    tail block gathered (the reference's embed/lm_head carve-out).
    """

    min_weight_size: int = 2**10
    reshard_after_forward: bool = True
    cpu_offload: bool = False  # params resident in host RAM, streamed per-step
    state_dict_type: str = "sharded"  # "sharded" | "full"
    activation_checkpointing: bool = False
    sharding_rules: Optional[list] = None  # extra (regex, PartitionSpec) pairs

    def __post_init__(self):
        if os.environ.get("FSDP_MIN_WEIGHT_SIZE"):
            self.min_weight_size = int(os.environ["FSDP_MIN_WEIGHT_SIZE"])
        if os.environ.get("FSDP_ACTIVATION_CHECKPOINTING"):
            self.activation_checkpointing = parse_flag_from_env("FSDP_ACTIVATION_CHECKPOINTING")
        if os.environ.get("FSDP_STATE_DICT_TYPE"):
            self.state_dict_type = os.environ["FSDP_STATE_DICT_TYPE"].lower()


@dataclass
class ContextParallelConfig(KwargsHandler):
    """Context-parallel (ring attention) config (reference
    TorchContextParallelConfig, utils/dataclasses.py:2208-2232).

    ``rotate_method``: "allgather" gathers all KV once; "alltoall" rotates KV
    shards around the cp ring (ring attention) — same vocabulary as the
    reference's ``set_rotate_method``; "zigzag" additionally balances causal
    work across ranks (each holds one early + one late sequence chunk) for
    ~2× causal ring efficiency — no reference equivalent.
    """

    rotate_method: str = "alltoall"
    use_pallas_kernel: bool = True
    causal: bool = True
    # chunk each ring step's kv shard so the score tile is
    # (b, h, sq_local, kv_block) instead of (b, h, sq_local, S/n) — the
    # memory bound long-context shards need; None = whole shard at once
    kv_block: Optional[int] = 2048

    def __post_init__(self):
        if self.rotate_method not in ("allgather", "alltoall", "zigzag"):
            raise ValueError(
                f"rotate_method must be allgather|alltoall|zigzag, got {self.rotate_method}"
            )
        if self.kv_block is not None and self.kv_block < 1:
            raise ValueError(f"kv_block must be None or >= 1, got {self.kv_block}")


@dataclass
class TensorParallelConfig(KwargsHandler):
    """TP knobs (reference TorchTensorParallelConfig,
    utils/dataclasses.py:2295-2314)."""

    tp_size: int = 1
    enable_async_tp: bool = False  # parity; XLA overlaps collectives itself
    sharding_rules: Optional[list] = None


@dataclass
class PipelineParallelConfig(KwargsHandler):
    """Training pipeline parallelism (native; the reference only pipelines
    inference via PiPPy — SURVEY §2.4 PP row)."""

    num_microbatches: int = 4
    # "1f1b": hand-scheduled one-forward-one-backward training pipeline with
    # a bounded (n_stages) activation ring (parallel/pp_1f1b.py). "gpipe":
    # forward pipeline + autodiff-transposed backward (parallel/pp.py) —
    # also what forward-only/eval paths always use.
    schedule: str = "1f1b"
    # >1 turns the 1f1b schedule into the Megatron-style INTERLEAVED
    # schedule (parallel/pp_interleaved.py): each device runs this many
    # non-adjacent layer chunks, shrinking the pipeline bubble ~1/v at the
    # cost of more in-flight activation memory. Requires num_microbatches
    # divisible by pp_size and layers divisible by pp_size*num_virtual_stages.
    num_virtual_stages: int = 1

    def __post_init__(self):
        if self.schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"Unknown pipeline schedule {self.schedule}")
        if self.num_virtual_stages < 1:
            raise ValueError("num_virtual_stages must be >= 1")
        if self.num_virtual_stages > 1 and self.schedule != "1f1b":
            raise ValueError(
                "num_virtual_stages > 1 requires the 1f1b schedule "
                "(interleaving is a 1F1B refinement)"
            )


@dataclass
class SequenceParallelConfig(KwargsHandler):
    """Ulysses-style SP (reference DeepSpeedSequenceParallelConfig,
    utils/dataclasses.py:2235-2292)."""

    sp_size: int = 1
    attention_heads_must_divide: bool = True


@dataclass
class ProfileKwargs(KwargsHandler):
    """Profiler config → jax.profiler (reference ProfileKwargs builds a
    torch.profiler.profile, utils/dataclasses.py:486-599)."""

    activities: Optional[list] = None
    schedule_option: Optional[dict] = None
    profile_memory: bool = False
    with_flops: bool = False
    record_shapes: bool = False
    with_stack: bool = False
    output_trace_dir: Optional[str] = None
    on_trace_ready: Optional[Callable] = None


# Registry used by Accelerator's kwargs_handlers argument
KWARGS_HANDLER_TYPES = (
    GradientAccumulationPlugin,
    AutocastKwargs,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    MixedPrecisionPolicy,
    DataLoaderConfiguration,
    ProjectConfiguration,
    ProfileKwargs,
)
