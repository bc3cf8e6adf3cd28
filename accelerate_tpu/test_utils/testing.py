"""Capability gating + subprocess self-launch helpers for the test suite.

The TPU-native counterpart of the reference's testing harness
(reference test_utils/testing.py:114-799): ``slow`` / ``require_*``
decorators gate tests on environment capabilities, and
``execute_subprocess`` / ``DEFAULT_LAUNCH_COMMAND`` drive scripts through
the real launcher the way the reference's self-launch tests do
(testing.py:781-799, DEFAULT_LAUNCH_COMMAND:114).

The decorators work on both pytest-style test functions and unittest
methods (they attach ``pytest.mark.skipif`` when pytest is importable,
falling back to ``unittest.skipUnless``).
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import unittest
from typing import Optional, Sequence

__all__ = [
    "parse_flag_from_env",
    "slow",
    "require_tpu",
    "require_cpu",
    "require_multidevice",
    "require_multihost",
    "require_module",
    "DEFAULT_LAUNCH_COMMAND",
    "execute_subprocess",
    "launch_script",
]


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key)
    if value is None:
        return default
    return value.lower() not in ("0", "false", "no", "off", "")


def _skip_unless(condition: bool, reason: str):
    """A decorator that skips when ``condition`` is false — pytest mark when
    available (works on plain functions), unittest otherwise."""
    try:
        import pytest

        return pytest.mark.skipif(not condition, reason=reason)
    except ImportError:  # pragma: no cover - pytest is baked into the image
        return unittest.skipUnless(condition, reason)


def slow(test_case):
    """Gate compile-heavy tests behind ``RUN_SLOW=1`` (the reference's slow
    gate, testing.py:160)."""
    return _skip_unless(
        parse_flag_from_env("RUN_SLOW"), "test is slow — set RUN_SLOW=1 to run"
    )(test_case)


def _backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001
        return "none"


def require_tpu(test_case):
    """Runs only on a real TPU backend (reference require_cuda/require_xpu
    analogue)."""
    return _skip_unless(_backend() == "tpu", "test requires a TPU backend")(test_case)


def require_cpu(test_case):
    return _skip_unless(_backend() == "cpu", "test requires the CPU backend")(test_case)


def require_multidevice(n: int = 2):
    """Decorator factory: runs only with >= n local devices (reference
    require_multi_device)."""

    def decorator(test_case):
        try:
            import jax

            count = jax.device_count()
        except Exception:  # noqa: BLE001
            count = 0
        return _skip_unless(count >= n, f"test requires >= {n} devices")(test_case)

    return decorator


def require_multihost(test_case):
    """Runs only in a multi-process (multi-host SPMD) job."""
    try:
        import jax

        count = jax.process_count()
    except Exception:  # noqa: BLE001
        count = 1
    return _skip_unless(count > 1, "test requires a multi-host run")(test_case)


def require_module(name: str):
    """Runs only when an optional dependency is importable (the role of the
    reference's require_wandb/require_tensorboard/... family)."""
    return _skip_unless(
        importlib.util.find_spec(name) is not None, f"test requires {name}"
    )


# The self-launch command every subprocess test goes through — the analogue of
# the reference's DEFAULT_LAUNCH_COMMAND (testing.py:114).
DEFAULT_LAUNCH_COMMAND = [
    sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
]


def cpu_spmd_env(n_devices: int = 8, **extra) -> dict:
    """Subprocess env for a virtual n-device CPU mesh (the conftest trick,
    exported for self-launch tests)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    # the package may be run from a source tree (not pip-installed): make the
    # subprocess resolve accelerate_tpu the same way this process does
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def execute_subprocess(
    cmd: Sequence[str],
    env: Optional[dict] = None,
    timeout: float = 900,
) -> subprocess.CompletedProcess:
    """Run a command, raising with FULL stdout/stderr on failure so test logs
    show the real error (reference execute_subprocess_async, testing.py:781)."""
    result = subprocess.run(
        list(cmd), env=env or os.environ.copy(),
        capture_output=True, text=True, timeout=timeout,
    )
    if result.returncode != 0:
        raise RuntimeError(
            f"command {list(cmd)} failed rc={result.returncode}\n"
            f"--- stdout ---\n{result.stdout}\n--- stderr ---\n{result.stderr}"
        )
    return result


def launch_script(
    script: str,
    script_args: Sequence[str] = (),
    launch_args: Sequence[str] = (),
    n_devices: int = 8,
    env: Optional[dict] = None,
    timeout: float = 900,
) -> subprocess.CompletedProcess:
    """Self-launch ``script`` through the real ``accelerate-tpu launch`` CLI
    on a virtual CPU mesh."""
    cmd = [*DEFAULT_LAUNCH_COMMAND, *launch_args, script, *script_args]
    return execute_subprocess(cmd, env=env or cpu_spmd_env(n_devices), timeout=timeout)
