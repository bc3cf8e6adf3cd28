"""In-process and multi-process launchers for notebooks and debugging.

TPU-native analogue of the reference's ``launchers.py`` (notebook_launcher:43,
debug_launcher:287). One JAX process already drives every local TPU chip, so
``notebook_launcher`` runs the function in-process by default; with
``num_processes > 1`` it forks REAL workers joined into a ``jax.distributed``
CPU cluster over localhost — actual multi-process SPMD semantics from a
single notebook cell (the reference forks torch processes with an elastic
rendezvous; same role). ``debug_launcher`` is the test-harness variant.
"""

from __future__ import annotations

import multiprocessing
import os
import socket
import sys
import traceback
from typing import Callable, Optional, Tuple

__all__ = ["notebook_launcher", "debug_launcher"]

from .logging import get_logger

logger = get_logger(__name__)


def _tpu_configured() -> bool:
    """Whether this environment targets TPU hardware — decided WITHOUT
    initializing jax (a process that initializes the TPU backend holds the
    chips, and the workers forked after it cannot have them).

    Env vars cover pod setups; the /dev/accel* / /dev/vfio device
    probes cover a bare TPU-VM host where jax auto-discovers the chips with
    no TPU env vars set at all — without them ``notebook_launcher(
    num_processes>1)`` would fork a CPU cluster and silently retarget
    training off the TPU. A pip-installed libtpu is deliberately NOT a
    signal: it proves software installation, not hardware (jax[tpu]-style
    images ship it on CPU-only hosts)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms:
        # an explicit JAX_PLATFORMS that excludes TPU (e.g. "cpu") wins over
        # hardware presence — it is the documented way to force the fork path
        return False
    if "tpu" in platforms or "TPU_NAME" in os.environ:
        return True
    import glob

    # v2-v4 expose numbered /dev/accelN nodes (the [0-9] avoids the generic
    # /dev/accel/ subsystem dir non-TPU NPUs create). v5e+ attach through
    # numbered vfio group nodes — but those also exist on GPU-passthrough
    # hypervisors, so vfio only counts when libtpu is importable too.
    if glob.glob("/dev/accel[0-9]*"):
        return True
    if glob.glob("/dev/vfio/[0-9]*"):
        import importlib.util

        return importlib.util.find_spec("libtpu") is not None
    return False


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cluster_worker(rank, num_processes, port, function, args, queue,
                    local_devices=1, extra_env=None):
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["ACCELERATE_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        os.environ["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
        os.environ["ACCELERATE_PROCESS_ID"] = str(rank)
        for key, value in (extra_env or {}).items():
            os.environ[key] = value
        # deterministic cluster size regardless of the parent's XLA_FLAGS
        # (pytest forces an 8-device host; workers are 1 device each unless
        # the caller asks otherwise). XLA_FLAGS is read at backend creation,
        # so rewriting it here — before any device query — is binding.
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={local_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
        import jax

        # the env var alone is NOT enough: unpickling ``function`` imported
        # its module, and jax with it, before this line ran, and jax read
        # JAX_PLATFORMS when it was imported
        jax.config.update("jax_platforms", "cpu")

        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=num_processes,
            process_id=rank,
        )
        function(*args)
        queue.put((rank, None))
    except Exception:  # noqa: BLE001 - reported to parent
        queue.put((rank, traceback.format_exc()))


def _spawn_cluster(function, args, num_processes, local_devices, port,
                   extra_env=None, timeout: Optional[float] = None):
    """Fork ``num_processes`` fresh interpreters, join them into one
    ``jax.distributed`` CPU cluster, run ``function(*args)`` on every rank,
    and surface any worker traceback in the parent."""
    ctx = multiprocessing.get_context("spawn")
    queue = ctx.Queue()
    procs = [
        ctx.Process(
            target=_cluster_worker,
            args=(r, num_processes, port, function, args, queue,
                  local_devices, extra_env),
        )
        for r in range(num_processes)
    ]
    for p in procs:
        p.start()
    timeout = timeout or float(
        os.environ.get("ACCELERATE_DEBUG_LAUNCHER_TIMEOUT", 600)
    )
    errors = []
    reported: set = set()
    try:
        for _ in procs:
            try:
                rank, err = queue.get(timeout=timeout)
                reported.add(rank)
            except Exception:
                # a worker died without reporting (OOM kill, segfault in
                # native code, sys.exit inside the function): name the
                # casualties instead of a bare queue.Empty, carry any
                # tracebacks ALREADY collected (often the root cause the
                # survivors are deadlocked on), and let finally reap the
                # survivors blocked in a collective waiting for the dead rank
                dead = [
                    f"rank {r} exitcode={p.exitcode}"
                    for r, p in enumerate(procs)
                    if p.exitcode is not None and r not in reported
                ]
                detail = "\n".join(errors)
                raise RuntimeError(
                    "launcher worker died without reporting "
                    f"({', '.join(dead) or 'all workers still alive'}); "
                    f"no result within {timeout:.0f}s"
                    + (f"\nreported failures so far:\n{detail}" if detail else "")
                ) from None
            if err is not None:
                errors.append(f"--- rank {rank} ---\n{err}")
    finally:
        for p in procs:
            p.join(timeout=60)
            if p.is_alive():
                p.terminate()
        # terminate() is SIGTERM: a worker wedged in native code (XLA
        # compile, collective) can survive it. Escalate: bounded re-join,
        # then SIGKILL, then a final join so no zombie outlives the launcher.
        for p in procs:
            if p.is_alive():
                p.join(timeout=10)
            if p.is_alive():
                logger.warning(
                    "launcher worker pid=%s survived terminate(); killing", p.pid
                )
                p.kill()
                p.join(timeout=10)
    if errors:
        raise RuntimeError("launcher worker failure:\n" + "\n".join(errors))


def notebook_launcher(
    function: Callable,
    args: Tuple = (),
    num_processes: int = None,
    mixed_precision: str = "no",
    use_port: Optional[str] = None,
    local_devices: int = 1,
    **kwargs,
) -> None:
    """Run a training function from a notebook (reference launchers.py:43-286).

    ``num_processes`` None/0/1 runs in-process: one JAX process already
    addresses every local TPU chip (multi-host notebooks attach via the
    coordinator env protocol). ``num_processes > 1`` forks that many REAL
    worker processes joined into a ``jax.distributed`` CPU cluster over
    localhost — each worker sees ``local_devices`` CPU devices, so a
    notebook cell gets genuine multi-process semantics (collectives, process
    indices, per-rank env) like the reference's fork path. ``use_port`` pins
    the coordinator port (default: a free one)."""
    fork = num_processes is not None and num_processes > 1
    if fork and _tpu_configured():
        # On a TPU host ONE process drives every chip: num_processes is
        # satisfied by SPMD, and forking would silently retarget training
        # onto CPU workers (JAX_PLATFORMS=cpu is forced in the worker).
        logger.warning(
            "notebook_launcher: TPU environment detected — running "
            "in-process (one JAX process drives all local chips; "
            "num_processes=%s is provided by SPMD). Set JAX_PLATFORMS=cpu "
            "to fork a real CPU jax.distributed cluster instead.",
            num_processes,
        )
        import jax

        if jax.process_count() == 1:
            n_local = len(jax.local_devices())
            if num_processes > n_local:
                raise ValueError(
                    f"num_processes={num_processes} but this host sees "
                    f"{n_local} devices and no multi-host coordinator is "
                    "configured (set ACCELERATE_COORDINATOR_ADDRESS/"
                    "NUM_PROCESSES/PROCESS_ID)."
                )
        fork = False
    if fork:
        # The reference refuses to fork once the accelerator is initialized
        # in the notebook kernel (its CUDA-already-initialized check,
        # launchers.py:160-175); same here: a parent holding a non-CPU JAX
        # backend cannot hand devices to forked workers.
        jax_mod = sys.modules.get("jax")
        if jax_mod is not None:
            try:
                backends = jax_mod._src.xla_bridge._backends  # noqa: SLF001
            except AttributeError:
                # private attr moved in a jax upgrade: make the drift
                # visible rather than silently skipping the guard (the
                # TPU-env check above still shields the dangerous case)
                logger.warning(
                    "notebook_launcher: cannot inspect jax backend state "
                    "(jax._src.xla_bridge._backends missing) — skipping the "
                    "already-initialized-accelerator check."
                )
                backends = {}
            if any(name not in ("cpu", "interpreter") for name in backends):
                raise RuntimeError(
                    "notebook_launcher(num_processes>1) must be called before "
                    "JAX initializes an accelerator backend in this kernel — "
                    "restart the notebook kernel and launch first (the "
                    "forked workers run a CPU jax.distributed cluster)."
                )
        extra_env = {}
        if mixed_precision != "no":
            extra_env["ACCELERATE_MIXED_PRECISION"] = mixed_precision
        port = int(use_port) if use_port else _free_port()
        _spawn_cluster(
            function, args, num_processes, local_devices, port,
            extra_env=extra_env,
        )
        return

    if mixed_precision != "no":
        os.environ.setdefault("ACCELERATE_MIXED_PRECISION", mixed_precision)
    function(*args)


def debug_launcher(function: Callable, args: Tuple = (), num_processes: int = 2, local_devices: int = 1) -> None:
    """Run ``function`` under a real ``num_processes``-process CPU JAX cluster
    (reference launchers.py:287 uses gloo FileStore; this is true SPMD)."""
    _spawn_cluster(function, args, num_processes, local_devices, _free_port())
