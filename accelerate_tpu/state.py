"""Process/device runtime state singletons.

TPU-native re-design of the reference's ``state.py``
(/root/reference/src/accelerate/state.py: ``PartialState``:123,
``AcceleratorState``:868, ``GradientState``:1231).

Key design departures from the reference, driven by the JAX runtime model:

* One process per **host**, not per device. ``jax.distributed.initialize``
  replaces the reference's backend zoo (``_prepare_backend``, state.py:755-817
  picking nccl/gloo/mpi/xccl/...): on TPU the collective fabric is ICI/DCN and
  XLA emits the collectives — there is no process-group selection to make.
* Device placement is implicit: SPMD arrays live on the whole mesh; there is
  no ``set_device`` (state.py:819) equivalent because a process addresses all
  of its local devices at once.
* The Borg-singleton pattern is kept (all instances share state) so that
  libraries can cheaply consult rank info anywhere, exactly like the
  reference's thread-shared ``_shared_state`` (state.py:91-119).
"""

from __future__ import annotations

import enum
import os
import threading
from contextlib import contextmanager
from typing import Any, Callable, Optional

from .utils.environment import parse_choice_from_env, parse_flag_from_env

__all__ = [
    "DistributedType",
    "PartialState",
    "AcceleratorState",
    "GradientState",
    "is_initialized",
]


class DistributedType(str, enum.Enum):
    """Runtime topology (reference utils/dataclasses.py DistributedType).

    Under GSPMD there is no per-strategy member (FSDP/DEEPSPEED/...):
    parallelism strategy is carried by :class:`ParallelismConfig`, not by the
    runtime type — a deliberate simplification over the reference, where the
    strategy engines force distinct code paths (state.py:972-1022).
    """

    NO = "NO"  # single device
    SPMD = "SPMD"  # one process, many local devices (jit/GSPMD)
    MULTI_HOST = "MULTI_HOST"  # many processes, SPMD over all devices


def _maybe_init_jax_distributed() -> None:
    """Initialize jax.distributed when launched multi-host.

    The launcher (commands/launch.py) sets ``ACCELERATE_COORDINATOR_ADDRESS``,
    ``ACCELERATE_NUM_PROCESSES`` and ``ACCELERATE_PROCESS_ID``; on Cloud TPU
    pods jax auto-discovers via metadata so initialize() needs no args.
    """
    import jax

    # Probe "already initialized" WITHOUT a backend query: jax.process_count()
    # initializes the XLA backend as a side effect, after which
    # jax.distributed.initialize refuses to run — the launcher env protocol
    # (this function's whole reason to exist) would always crash. Found by
    # the 4-process supervisor test; the debug_launcher path masked it by
    # initializing distributed itself before PartialState.
    if jax.distributed.is_initialized():
        return
    coord = os.environ.get("ACCELERATE_COORDINATOR_ADDRESS")
    nproc = os.environ.get("ACCELERATE_NUM_PROCESSES")
    if coord and nproc and int(nproc) > 1:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(os.environ.get("ACCELERATE_PROCESS_ID", "0")),
        )


def _coordination_client():
    """The jax.distributed coordination-service client, or None when this
    process is not part of a distributed job."""
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client
    except (ImportError, AttributeError):
        return None


# wait_at_barrier requires a fresh barrier id per rendezvous; a per-tag
# counter keeps ids aligned across processes because barriers are
# collective (every process reaches the same sites in the same order).
_BARRIER_SEQ: dict = {}

# The coordination service requires a FINITE wait on every blocking call,
# so "unbounded" (ACCELERATE_BARRIER_TIMEOUT unset or 0) becomes a 7-day
# sentinel — long enough to outlive any real recovery window, and the
# error message says so instead of promising an unbounded wait the
# service cannot deliver.
_UNBOUNDED_WAIT_MS = 7 * 24 * 3_600_000


def _service_wait_ms(timeout: Optional[float]) -> int:
    """Milliseconds bound for a coordination-service blocking call,
    honoring ``ACCELERATE_BARRIER_TIMEOUT`` when ``timeout`` is None."""
    if timeout is None:
        raw = os.environ.get("ACCELERATE_BARRIER_TIMEOUT", "")
        timeout = float(raw) if raw else None
    return int(timeout * 1000) if timeout and timeout > 0 else _UNBOUNDED_WAIT_MS


def _coordination_barrier(client, tag: str, timeout: Optional[float]) -> None:
    """Host-level barrier over the coordination service (pure gRPC — no XLA
    program). This is the barrier path on CPU multiprocess clusters, where
    this jaxlib cannot run cross-process XLA computations at all; elastic
    recovery's consensus and replica-restore barriers must still work
    there (a gang restart is exactly when the cluster is least healthy)."""
    seq = _BARRIER_SEQ.get(tag, 0)
    _BARRIER_SEQ[tag] = seq + 1
    bounded = bool(timeout and timeout > 0)
    ms = _service_wait_ms(timeout)
    try:
        client.wait_at_barrier(f"{tag}#{seq}", ms)
    except Exception as e:  # noqa: BLE001 — typed below
        from .utils.fault import BarrierTimeoutError

        hint = (
            "(set ACCELERATE_BARRIER_TIMEOUT=0 to wait the coordination "
            "service's 7-day cap — the service requires a finite bound)"
            if bounded
            else "(this was the 7-day 'unbounded' cap; the coordination "
            "service requires a finite bound)"
        )
        raise BarrierTimeoutError(
            f"barrier {tag!r} did not complete within {ms / 1000:g}s — a "
            f"peer process is likely dead or wedged {hint}"
        ) from e


def _run_with_barrier_timeout(sync_fn: Callable[[], Any], tag: str, timeout: Optional[float]) -> None:
    """Run a blocking barrier with an optional upper bound.

    The underlying collective blocks in native code and cannot be
    cancelled; on timeout the barrier thread is abandoned (daemonized) and
    a typed :class:`~accelerate_tpu.utils.fault.BarrierTimeoutError` is
    raised — the caller is expected to exit, which is exactly what the
    launch supervisor wants: a precise failure naming the barrier site
    instead of a stale-heartbeat kill minutes later. ``timeout`` of
    ``None``/``0`` runs the barrier inline with original semantics."""
    if not timeout or timeout <= 0:
        sync_fn()
        return
    done = threading.Event()
    errors: list[BaseException] = []

    def _run():
        try:
            sync_fn()
        except BaseException as e:  # noqa: BLE001 — reraised on caller thread
            errors.append(e)
        finally:
            done.set()

    t = threading.Thread(target=_run, name=f"barrier:{tag}", daemon=True)
    t.start()
    if not done.wait(timeout):
        from .utils.fault import BarrierTimeoutError

        # The native collective cannot be cancelled: the thread stays
        # abandoned (daemon) on this path by design, and the caller exits.
        raise BarrierTimeoutError(
            f"barrier {tag!r} did not complete within {timeout:g}s — a peer "
            "process is likely dead or wedged (set ACCELERATE_BARRIER_TIMEOUT"
            "=0 to restore unbounded waits)"
        )
    # Success: done is set inside the thread's finally, so the thread is
    # within microseconds of exiting — the bounded join retires it instead
    # of leaking one "barrier:<tag>" thread per successful timed barrier.
    t.join(timeout=1.0)
    if errors:
        raise errors[0]


class PartialState:
    """Borg singleton exposing process/device/rank info and process-control
    helpers (reference state.py:123-867)."""

    _shared_state: dict[str, Any] = {}
    _lock = threading.Lock()

    def __init__(self, cpu: bool = False, _allow_uninitialized: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        with self._lock:
            if self.initialized:
                return
            self._init(cpu=cpu, **kwargs)

    def _init(self, cpu: bool = False, **kwargs):
        import jax

        if cpu or parse_flag_from_env("ACCELERATE_USE_CPU"):
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _maybe_init_jax_distributed()

        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        # One process per host in JAX: the local index is the rank within the
        # node, which for the supported launchers equals 0 unless multiple
        # processes share a host (possible with JAX_PLATFORMS=cpu testing).
        self.local_process_index = int(os.environ.get("ACCELERATE_LOCAL_PROCESS_ID", 0))
        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.num_devices = len(self.devices)
        self.num_local_devices = len(self.local_devices)
        self.device = self.local_devices[0]
        self.platform = self.device.platform  # "tpu" | "cpu" | "gpu"
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif self.num_devices > 1:
            self.distributed_type = DistributedType.SPMD
        else:
            self.distributed_type = DistributedType.NO
        self.initialized = True

    # ------------------------------------------------------------------ info
    @property
    def initialized(self) -> bool:
        return self._shared_state.get("initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["initialized"] = value

    @property
    def use_distributed(self) -> bool:
        return self.num_processes > 1 or self.num_devices > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def preemption_requested(self) -> bool:
        """Whether a handled SIGTERM/SIGINT has arrived in this process
        (set by ``utils.fault``'s preemption handler). Training loops can
        poll this to break out at a step boundary instead of relying on the
        handler's emergency save."""
        return self._shared_state.get("preemption_requested", False)

    def __repr__(self) -> str:
        return (
            f"PartialState(distributed_type={self.distributed_type.value}, "
            f"num_processes={self.num_processes}, process_index={self.process_index}, "
            f"num_devices={self.num_devices}, platform={self.platform!r})"
        )

    @property
    def default_device(self):
        """The first addressable device (reference state.py default_device
        picks cuda/mps/cpu; here the backend's first device)."""
        return self.device

    def set_device(self) -> None:
        """No-op by design (reference state.py:819 binds one process to one
        accelerator): under SPMD a process addresses ALL its local devices
        and placement is the mesh's job."""

    # --------------------------------------------------------- process control
    def wait_for_everyone(
        self,
        tag: str = "accelerate_tpu.wait_for_everyone",
        timeout: Optional[float] = None,
    ) -> None:
        """Cross-process barrier (reference state.py:377-414; the xla branch
        uses ``xm.rendezvous``). Implemented as a named sync over all global
        devices; a no-op single-process.

        A dead peer host makes this hang forever. ``timeout`` (seconds; or
        the ``ACCELERATE_BARRIER_TIMEOUT`` env var — unset/0 preserves the
        blocking semantics) bounds the wait and raises a typed
        :class:`~accelerate_tpu.utils.fault.BarrierTimeoutError` naming the
        barrier site ``tag``, so the launch supervisor gets a precise error
        instead of a stale-heartbeat kill."""
        if self.num_processes <= 1:
            return
        import jax

        from jax.experimental import multihost_utils

        if timeout is None:
            raw = os.environ.get("ACCELERATE_BARRIER_TIMEOUT", "")
            timeout = float(raw) if raw else None
        client = _coordination_client()
        if client is not None and jax.default_backend() == "cpu":
            # this jaxlib's CPU backend cannot run multiprocess XLA
            # computations, so sync_global_devices (a jitted psum) would
            # fail; rendezvous over the coordination service instead
            _coordination_barrier(client, tag, timeout)
            return
        _run_with_barrier_timeout(
            lambda: multihost_utils.sync_global_devices(tag), tag, timeout
        )

    def gather_object(self, obj):
        """All-gather one picklable host object per process; returns the list
        indexed by process rank (single-process: ``[obj]``). This is the
        consensus primitive of elastic recovery: each host contributes its
        local view of the checkpoint tree and every host sees all views.
        Collective — every process must call it together."""
        if self.num_processes <= 1:
            return [obj]
        # _object_allgather keeps exactly one element per rank (the public
        # ops.gather_object flattens list payloads, which would corrupt a
        # host view that happens to be a list).
        from .ops.operations import _object_allgather

        return _object_allgather(obj)

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array evenly across processes, yielding this
        process's slice (reference state.py:426-512). With ``apply_padding``
        the last elements are repeated so all slices have equal length."""
        if self.num_processes == 1:
            yield inputs
            return
        import math

        length = len(inputs)
        num_samples_per_process = math.ceil(length / self.num_processes)
        start = self.process_index * num_samples_per_process
        end = start + num_samples_per_process

        if isinstance(inputs, dict):
            sliced = {}
            for k, v in inputs.items():
                if len(v) != length:
                    raise ValueError(
                        f"All dict values must share length; {k!r} has {len(v)} != {length}"
                    )
                sliced[k] = self._slice_with_padding(v, start, end, apply_padding)
            yield sliced
        else:
            yield self._slice_with_padding(inputs, start, end, apply_padding)

    @staticmethod
    def _slice_with_padding(seq, start, end, apply_padding):
        import numpy as np

        part = seq[start:end]
        if apply_padding and len(part) < (end - start) and len(seq) > 0:
            missing = (end - start) - len(part)
            if isinstance(seq, np.ndarray):
                pad = np.repeat(seq[-1:], missing, axis=0)
                part = np.concatenate([part, pad], axis=0) if len(part) else pad
            else:
                part = list(part) + [seq[-1]] * missing
        return part

    @contextmanager
    def main_process_first(self):
        """Main process runs the body first, others wait; then the rest run
        (reference state.py:513-554). Guards e.g. dataset cache writes.

        Both halves pass the SAME tagged barrier exactly once per rank —
        non-main ranks arrive before the body, main arrives after it, and
        the barrier releases everyone together. Divergent enter/exit tags
        would key two different barriers that can never pair (every rank
        must agree on the barrier name), wedging the gang."""
        if not self.is_main_process:
            # graft: gang-ok — paired barrier: every rank passes this one tag exactly once (non-main here, main below)
            self.wait_for_everyone("accelerate_tpu.state.main_process_first")
        yield
        if self.is_main_process:
            # graft: gang-ok — second half of the paired barrier above
            self.wait_for_everyone("accelerate_tpu.state.main_process_first")

    @contextmanager
    def local_main_process_first(self):
        if not self.is_local_main_process:
            # graft: gang-ok — paired barrier, same tag on both rank branches (see main_process_first)
            self.wait_for_everyone("accelerate_tpu.state.local_main_process_first")
        yield
        if self.is_local_main_process:
            # graft: gang-ok — second half of the paired barrier above
            self.wait_for_everyone("accelerate_tpu.state.local_main_process_first")

    def on_main_process(self, function: Callable) -> Callable:
        """Decorator: run only on the main process (reference state.py:555)."""

        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None) -> Callable:
        if function is None:
            import functools

            return functools.partial(self.on_process, process_index=process_index)

        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def on_local_process(self, function: Callable = None, local_process_index: int = None) -> Callable:
        if function is None:
            import functools

            return functools.partial(
                self.on_local_process, local_process_index=local_process_index
            )

        def wrapper(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return wrapper

    def print(self, *args, **kwargs) -> None:
        if self.is_main_process:
            print(*args, **kwargs)

    # ----------------------------------------------------------------- reset
    @classmethod
    def _reset_state(cls) -> None:
        """Testing hook, mirrors reference AcceleratorState._reset_state."""
        cls._shared_state.clear()

    def destroy_process_group(self) -> None:
        """Shut down the jax.distributed client (reference destroys the torch
        process group, state.py:737-754)."""
        import jax

        if self.num_processes > 1:
            jax.distributed.shutdown()


class AcceleratorState:
    """Adds precision/parallelism/mesh state on top of PartialState
    (reference state.py:868-1230)."""

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        parallelism_config=None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self.mixed_precision:
                raise ValueError(
                    "AcceleratorState already initialized with "
                    f"mixed_precision={self.mixed_precision!r}; cannot re-init with "
                    f"{mixed_precision!r}. Call AcceleratorState._reset_state() first "
                    "(reference state.py:1047 _check_initialized)."
                )
            return
        self._partial = PartialState(cpu=cpu)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env("ACCELERATE_MIXED_PRECISION", "no")
        mixed_precision = str(mixed_precision).lower()
        if mixed_precision not in ("no", "bf16", "fp16", "fp8"):
            raise ValueError(
                f"Unknown mixed_precision {mixed_precision!r}; choose from no|bf16|fp16|fp8"
            )
        self.mixed_precision = mixed_precision
        if parallelism_config is None:
            from .parallelism_config import ParallelismConfig

            parallelism_config = ParallelismConfig.from_env(total_devices=self._partial.num_devices)
        self.parallelism_config = parallelism_config
        self.mesh = None  # built lazily via get_device_mesh()
        self.initialized = True

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["initialized"] = value

    def get_device_mesh(self):
        if self.mesh is None:
            self.mesh = self.parallelism_config.build_device_mesh(self._partial.platform)
        return self.mesh

    @property
    def is_fsdp2(self) -> bool:
        """Reference: fsdp_version == 2; parameter sharding here IS
        per-tensor (fsdp2-style) whenever dp_shard is active."""
        pcfg = self._shared_state.get("parallelism_config")
        return bool(pcfg is not None and pcfg.fsdp_enabled)

    @property
    def fork_launched(self) -> bool:
        """Always False: processes come from the launcher, never fork
        (reference tracks notebook fork launches)."""
        return False

    @property
    def deepspeed_plugin(self):
        """Always None — no DeepSpeed engine; ZeRO is mesh shardings
        (docs/usage_guides/zero_on_tpu.md)."""
        return None

    def get_deepspeed_plugin(self, name: str):
        raise ValueError(
            "no DeepSpeed plugins exist here — ZeRO semantics are mesh "
            "shardings (docs/usage_guides/zero_on_tpu.md)"
        )

    def select_deepspeed_plugin(self, name: str):
        raise ValueError(
            "no DeepSpeed plugins exist here — ZeRO semantics are mesh "
            "shardings (docs/usage_guides/zero_on_tpu.md)"
        )

    # Proxy the PartialState surface (reference state.py does the same via
    # __getattr__ against PartialState._shared_state).
    def __getattr__(self, name: str):
        if name in ("_shared_state", "__dict__"):
            raise AttributeError(name)
        partial = self._shared_state.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(f"AcceleratorState has no attribute {name!r}")

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Singleton tracking gradient-accumulation sync state and dataloader end
    detection (reference state.py:1231-1371).

    Under JAX the accumulation arithmetic itself lives inside the compiled
    train step (see optimizer.py); this object carries the *bookkeeping* the
    eager loop observes: ``sync_gradients``, ``end_of_dataloader``,
    ``remainder``, and the registry of active dataloaders.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin=None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = []
            self.plugin_kwargs = {}
            self._num_steps = 1
            self.initialized = True
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()
            self._num_steps = gradient_accumulation_plugin.num_steps

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["initialized"] = value

    @property
    def num_steps(self) -> int:
        return self._num_steps

    @num_steps.setter
    def num_steps(self, value: int) -> None:
        self._num_steps = value

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def end_of_dataloader(self) -> bool:
        if self.active_dataloader is None:
            return False
        return getattr(self.active_dataloader, "end_of_dataloader", False)

    @property
    def remainder(self) -> int:
        """Number of extra (duplicated) samples in the final padded batch; -1
        when unknown (reference state.py:1298)."""
        if self.active_dataloader is None:
            return -1
        return getattr(self.active_dataloader, "remainder", -1)

    def _set_sync_gradients(self, value: bool) -> None:
        self.sync_gradients = value

    @property
    def is_xla_gradients_synced(self) -> bool:
        """Always True: gradients are values of one compiled SPMD program —
        there is no lazy-tensor mark_step whose completion the reference
        must track (state.py is_xla_gradients_synced)."""
        return True

    @is_xla_gradients_synced.setter
    def is_xla_gradients_synced(self, value) -> None:
        """Accepted and ignored (reference code assigns this around backward/
        step to track mark_step completion; there is nothing to track)."""

    def _add_dataloader(self, dataloader) -> None:
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1] if self.dataloader_references else None

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()


def is_initialized() -> bool:
    """Whether AcceleratorState has been initialized (reference state.py)."""
    return AcceleratorState._shared_state.get("initialized", False)
