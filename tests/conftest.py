"""Test harness: force a virtual 8-device CPU platform before jax imports.

This mirrors (and strengthens — real SPMD semantics, not a gloo fork) the
reference's CPU-multiprocess test trick (`debug_launcher`, SURVEY §4): all
sharding/mesh tests run on 8 virtual CPU devices.
"""

import os

# Force-override: whatever platform the session environment names, the test
# suite always runs on virtual CPU devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# jax reads JAX_PLATFORMS when it is imported; a plugin that imported it before
# this file ran would have captured another value — override via jax.config too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy test, skipped unless RUN_SLOW=1 (reference RUN_SLOW gate)",
    )


@pytest.fixture(scope="session", autouse=True)
def _tracing_dumps_to_tmp(tmp_path_factory):
    """Point the default tracer's flight dumps at a session tmp dir —
    worker-death tests would otherwise litter runs/ with flight-*.json
    on every suite run. Tests that need their own tracer (test_tracing)
    still configure/replace the default themselves."""
    from accelerate_tpu import tracing
    from accelerate_tpu.utils.dataclasses import TracingConfig

    tracing.configure(TracingConfig(
        dump_dir=str(tmp_path_factory.mktemp("flight_dumps"))
    ))
    yield


def pytest_collection_modifyitems(config, items):
    """Without RUN_SLOW=1, skip tests marked slow — keeps the default suite
    inside a CI-sized budget; `make test_all` runs everything."""
    from accelerate_tpu.test_utils.testing import parse_flag_from_env

    if parse_flag_from_env("RUN_SLOW"):
        return
    skip = pytest.mark.skip(reason="slow — set RUN_SLOW=1 to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def fault_inject():
    """Arm the ``ACCELERATE_TPU_FAULT_INJECT`` hook for one test and always
    disarm it afterwards (a leaked spec would kill unrelated tests' saves).
    Yields a setter: ``fault_inject("before_commit:raise")``."""
    from accelerate_tpu.utils.fault import FAULT_INJECT_ENV

    def _arm(spec: str) -> None:
        os.environ[FAULT_INJECT_ENV] = spec

    try:
        yield _arm
    finally:
        os.environ.pop(FAULT_INJECT_ENV, None)
        os.environ.pop("ACCELERATE_TPU_FAULT_SEED", None)
        # clear per-entry hit counters / flaky RNG streams and release any
        # hang latch a test left armed (a parked probe thread must not
        # outlive its test)
        from accelerate_tpu.utils.fault import reset_fault_state

        reset_fault_state()


@pytest.fixture(autouse=True)
def reset_state():
    """Reset the Borg singletons between tests (the analogue of the
    reference's AccelerateTestCase.tearDown → _reset_state())."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
