"""Resilient-serving suite (docs/serving.md): backpressure, deadlines,
retry/backoff, circuit breaking, degradation, drain, fault injection.

Most tests drive :class:`InferenceServer` with an injected ``generate_fn``
so each failure mode is exercised deterministically and fast (no jit);
``test_real_model_end_to_end`` closes the loop against the real compiled
``generate`` path on a tiny llama.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from accelerate_tpu.serving import InferenceServer, ServingResult
from accelerate_tpu.telemetry import LatencyReservoir
from accelerate_tpu.utils.dataclasses import ServingConfig
from accelerate_tpu.utils import fault
from accelerate_tpu.utils.fault import (
    BatchExecutionError,
    CircuitOpenError,
    RequestDeadlineExceeded,
    ServerDrainingError,
    ServerOverloaded,
)


def echo_gen(batches=None, delay=0.0):
    """Fake generate_fn: appends `max_new_tokens` copies of each row's first
    token; optionally records every executed batch's (shape, budget)."""

    def fn(model, ids, max_new_tokens=8, **kw):
        if batches is not None:
            batches.append((ids.shape, max_new_tokens))
        if delay:
            time.sleep(delay)
        new = np.repeat(ids[:, :1], max_new_tokens, axis=1)
        return np.concatenate([ids, new], axis=1)

    return fn


def wait_until(predicate, timeout=5.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ------------------------------------------------------------------ batching
def test_batches_coalesce_and_rows_route_back():
    batches = []
    cfg = ServingConfig(max_batch_size=4, batch_window_s=0.05, batch_bucket=False)
    with InferenceServer(object(), cfg, generate_fn=echo_gen(batches)) as srv:
        prompts = [np.full(5, i, dtype=np.int32) for i in range(4)]
        futs = [srv.submit(p, max_new_tokens=3) for p in prompts]
        results = [f.result(5) for f in futs]
    # all four rode ONE batch, and each got ITS row back
    assert batches == [((4, 5), 3)]
    for i, res in enumerate(results):
        assert isinstance(res, ServingResult)
        assert res.batch_size == 4
        np.testing.assert_array_equal(res.tokens, np.full(8, i, dtype=np.int32))
    assert srv.metrics["completed"] == 4
    assert srv.metrics["batches"] == 1


def test_batch_rows_padded_to_pow2_bucket():
    batches = []
    cfg = ServingConfig(max_batch_size=8, batch_window_s=0.05)
    with InferenceServer(object(), cfg, generate_fn=echo_gen(batches)) as srv:
        futs = [srv.submit(np.arange(4), max_new_tokens=2) for _ in range(3)]
        [f.result(5) for f in futs]
    # 3 live rows execute as a 4-row bucket (compiled-program LRU sees pow-2
    # batch shapes only), but only the real rows reply
    assert batches == [((4, 4), 2)]
    assert srv.metrics["completed"] == 3


def test_incompatible_requests_split_batches():
    batches = []
    cfg = ServingConfig(max_batch_size=8, batch_window_s=0.05, batch_bucket=False)
    with InferenceServer(object(), cfg, generate_fn=echo_gen(batches)) as srv:
        f1 = srv.submit(np.arange(4), max_new_tokens=2)
        f2 = srv.submit(np.arange(6), max_new_tokens=2)  # different prompt len
        f1.result(5), f2.result(5)
    assert len(batches) == 2


# -------------------------------------------------------------- backpressure
def test_queue_full_rejects_with_typed_error():
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_queue=2, max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    try:
        first = srv.submit(np.arange(3))
        # wait until the worker holds `first` in flight, then fill the queue
        assert wait_until(lambda: srv.queue_depth() == 0)
        queued = [srv.submit(np.arange(3)) for _ in range(2)]
        with pytest.raises(ServerOverloaded):
            srv.submit(np.arange(3))
        assert srv.metrics["rejected_queue_full"] == 1
        gate.set()
        for f in [first, *queued]:
            # a full queue is 100% occupancy: the degradation ladder may
            # clamp budgets, but every admitted request still completes
            assert f.result(5).tokens.shape[0] >= 3
        assert srv.metrics["completed"] == 3
    finally:
        gate.set()
        srv.close()


def test_overload_rejection_carries_retry_after_hint():
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_queue=2, max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    try:
        srv.submit(np.arange(3))
        assert wait_until(lambda: srv.queue_depth() == 0)
        for _ in range(2):
            srv.submit(np.arange(3))
        with pytest.raises(ServerOverloaded) as exc_info:
            srv.submit(np.arange(3))
        # the hint is EWMA-derived, positive, bounded, and in the message
        hint = exc_info.value.retry_after_s
        assert hint is not None and 0.0 < hint <= 5.0
        assert "resubmit" in str(exc_info.value)
    finally:
        gate.set()
        srv.close()


def test_draining_rejection_hints_zero_retry_after():
    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=echo_gen())
    srv.close()
    with pytest.raises(ServerDrainingError) as exc_info:
        srv.submit(np.arange(3))
    # draining = permanent for THIS replica: retry elsewhere immediately
    assert exc_info.value.retry_after_s == 0.0


# ------------------------------------------------------------------ deadlines
def test_deadline_shed_at_dequeue():
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    try:
        blocker = srv.submit(np.arange(3))  # occupies the worker
        assert wait_until(lambda: srv.queue_depth() == 0)
        doomed = srv.submit(np.arange(3), deadline_s=0.001)
        time.sleep(0.05)  # deadline passes while queued behind the blocker
        gate.set()
        with pytest.raises(RequestDeadlineExceeded):
            doomed.result(5)
        assert blocker.result(5).tokens is not None
        assert srv.metrics["shed_deadline"] == 1
        # the shed request never reached the executor (no wasted batch slot)
        assert srv.metrics["batches"] == 1
    finally:
        gate.set()
        srv.close()


def test_deadline_enforced_at_completion():
    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    with InferenceServer(
        object(), cfg, generate_fn=echo_gen(delay=0.08)
    ) as srv:
        # est batch time is 0 on the first batch, so it is NOT shed at
        # dequeue — it completes late and fails the completion-time check
        f = srv.submit(np.arange(3), deadline_s=0.02)
        with pytest.raises(RequestDeadlineExceeded):
            f.result(5)
        assert srv.metrics["completed_late"] == 1


def test_cancelled_request_does_not_kill_worker():
    """A client cancelling its pending Future (client-side timeout) must not
    crash the dispatch loop when the worker later tries to shed/resolve it
    (regression: InvalidStateError killed the worker)."""
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    try:
        blocker = srv.submit(np.arange(3))
        assert wait_until(lambda: srv.queue_depth() == 0)
        doomed = srv.submit(np.arange(3), deadline_s=0.001)
        assert doomed.cancel()  # client gave up while still queued
        time.sleep(0.05)  # its deadline passes behind the blocker
        gate.set()
        assert blocker.result(5).tokens is not None
        # the worker survived resolving the cancelled request: still serving
        assert srv.submit(np.arange(3)).result(5).tokens is not None
        assert srv.metrics["shed_deadline"] == 0  # cancelled, not shed
    finally:
        gate.set()
        srv.close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_worker_death_fails_fast_instead_of_hanging():
    """When the dispatch worker dies, the in-flight batch's futures resolve
    with a typed error and later submit() calls fail fast — nothing hangs
    on a queue no loop consumes anymore."""

    def lethal(model, ids, **kw):
        raise SystemExit(3)  # not retried: kills the worker thread

    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=lethal)
    f = srv.submit(np.arange(3))
    with pytest.raises(BatchExecutionError):
        f.result(5)
    assert srv._drained.wait(5)  # worker exited, queue rejected
    with pytest.raises(ServerDrainingError) as exc_info:
        srv.submit(np.arange(3))
    assert "worker died" in str(exc_info.value)
    assert exc_info.value.retriable  # a healthy replica can take it


# ------------------------------------------------------------ retry / breaker
def test_retry_recovers_after_transient_failures():
    state = {"fails": 2}

    def flaky(model, ids, max_new_tokens=4, **kw):
        if state["fails"] > 0:
            state["fails"] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: transient")
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(
        max_retries=3, retry_backoff_s=0.002, retry_backoff_max_s=0.01,
        breaker_threshold=10,
    )
    with InferenceServer(object(), cfg, generate_fn=flaky) as srv:
        res = srv.submit(np.arange(3), max_new_tokens=4).result(5)
        assert res.tokens.shape == (7,)
        assert srv.metrics["retries"] == 2
        assert srv.metrics["batch_failures"] == 2
        assert srv.metrics["completed"] == 1


def test_retry_gives_up_after_budget():
    def broken(model, ids, **kw):
        raise RuntimeError("permanently broken")

    cfg = ServingConfig(
        max_retries=1, retry_backoff_s=0.002, retry_backoff_max_s=0.01,
        breaker_threshold=10,
    )
    with InferenceServer(object(), cfg, generate_fn=broken) as srv:
        f = srv.submit(np.arange(3))
        with pytest.raises(BatchExecutionError) as exc_info:
            f.result(5)
        assert "2 attempt(s)" in str(exc_info.value)
        assert isinstance(exc_info.value.__cause__, RuntimeError)
        assert srv.metrics["retries"] == 1
        assert srv.metrics["batch_failures"] == 2


def test_breaker_open_half_open_close_cycle():
    state = {"broken": True}

    def fn(model, ids, max_new_tokens=4, **kw):
        if state["broken"]:
            raise RuntimeError("backend down")
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(
        max_retries=0, breaker_threshold=2, breaker_reset_s=0.15,
        max_batch_size=1, batch_window_s=0.0,
    )
    srv = InferenceServer(object(), cfg, generate_fn=fn)
    try:
        for _ in range(2):
            with pytest.raises(BatchExecutionError):
                srv.submit(np.arange(3)).result(5)
        # OPEN: fail fast at admission
        assert wait_until(lambda: srv._breaker.rejects_admission)
        with pytest.raises(CircuitOpenError):
            srv.submit(np.arange(3))
        assert srv.metrics["breaker_opens"] == 1
        assert srv.metrics["rejected_breaker"] == 1

        # reset window passes with the backend still broken: the HALF_OPEN
        # probe fails and re-opens
        time.sleep(0.2)
        with pytest.raises(BatchExecutionError):
            srv.submit(np.arange(3)).result(5)
        assert wait_until(lambda: srv._breaker.rejects_admission)

        # backend recovers: next probe closes the breaker
        state["broken"] = False
        time.sleep(0.2)
        res = srv.submit(np.arange(3)).result(5)
        assert res.tokens.shape == (35,)
        assert not srv._breaker.rejects_admission
        # traffic flows normally again
        assert srv.submit(np.arange(3)).result(5).tokens.shape == (35,)
    finally:
        srv.close()


# ----------------------------------------------------------------- drain path
def test_drain_completes_inflight_and_rejects_queued():
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0, max_queue=16)
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    inflight = srv.submit(np.arange(3))
    assert wait_until(lambda: srv.queue_depth() == 0)
    queued = [srv.submit(np.arange(3)) for _ in range(3)]

    t = threading.Thread(target=lambda: (time.sleep(0.05), gate.set()))
    t.start()
    assert srv.close(drain=True, timeout=5)
    t.join()

    # in-flight batch finished and replied; queued got a retriable rejection
    assert inflight.result(1).tokens.shape == (35,)
    for f in queued:
        with pytest.raises(ServerDrainingError) as exc_info:
            f.result(1)
        assert exc_info.value.retriable
    with pytest.raises(ServerDrainingError):
        srv.submit(np.arange(3))
    assert srv.metrics["rejected_draining"] == 4  # 3 queued + 1 post-drain


def test_half_open_probe_races_concurrent_submits():
    """PR-10 satellite: many threads submit the instant the breaker's reset
    window elapses. Exactly one HALF_OPEN probe batch must execute (batch
    capped at 1), and whatever the race outcome, every future resolves —
    a success closes the breaker, admission races get typed retriable
    CircuitOpenError, and nothing hangs."""
    state = {"broken": True}
    executed = []

    def fn(model, ids, max_new_tokens=4, **kw):
        executed.append(ids.shape[0])
        if state["broken"]:
            raise RuntimeError("backend down")
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(
        max_retries=0, breaker_threshold=1, breaker_reset_s=0.15,
        max_batch_size=8, batch_window_s=0.01, max_queue=64,
    )
    srv = InferenceServer(object(), cfg, generate_fn=fn)
    try:
        with pytest.raises(BatchExecutionError):
            srv.submit(np.arange(3)).result(5)
        assert wait_until(lambda: srv._breaker.rejects_admission)
        state["broken"] = False
        time.sleep(0.2)  # reset window elapsed: next state() is HALF_OPEN

        futures, errors = [], []
        barrier = threading.Barrier(8)

        def race():
            barrier.wait(timeout=5)
            try:
                futures.append(srv.submit(np.arange(3)))
            except CircuitOpenError as exc:
                assert exc.retriable
                errors.append(exc)

        threads = [threading.Thread(target=race) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5)
        # every admitted future resolves; none hang on the probe race
        for f in futures:
            assert f.result(5).tokens.shape == (35,)
        assert len(futures) + len(errors) == 8
        # the HALF_OPEN probe ran alone: the first post-recovery batch had
        # exactly one row, regardless of how many submits raced it
        post_recovery = executed[1:]
        assert post_recovery and post_recovery[0] == 1
        assert not srv._breaker.rejects_admission
        assert srv.submit(np.arange(3)).result(5).tokens.shape == (35,)
    finally:
        srv.close()


def test_concurrent_submits_during_drain_resolve_typed():
    """PR-10 satellite: submits racing a drain never hang — each either
    completes (admitted before the drain flag) or raises/receives a typed
    retriable ServerDrainingError a fleet router can transparently retry."""
    gate = threading.Event()

    def gated(model, ids, max_new_tokens=4, **kw):
        gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0, max_queue=64)
    srv = InferenceServer(object(), cfg, generate_fn=gated, replica_id="rX")
    inflight = srv.submit(np.arange(3))
    assert wait_until(lambda: srv.queue_depth() == 0)

    outcomes = []
    start = threading.Barrier(9)

    def submitter():
        start.wait(timeout=5)
        try:
            fut = srv.submit(np.arange(3))
        except ServerDrainingError as exc:
            outcomes.append(("sync", exc))
            return
        try:
            outcomes.append(("ok", fut.result(10)))
        except ServerDrainingError as exc:
            outcomes.append(("async", exc))

    threads = [threading.Thread(target=submitter) for _ in range(8)]
    for t in threads:
        t.start()
    start.wait(timeout=5)
    time.sleep(0.01)
    gate.set()
    assert srv.close(drain=True, timeout=10)
    for t in threads:
        t.join(timeout=10)

    assert inflight.result(1).tokens.shape == (35,)
    assert len(outcomes) == 8  # zero hung/dropped racers
    for kind, out in outcomes:
        if kind == "ok":
            assert out.tokens.shape == (35,)
        else:
            assert out.retriable and out.replica_id == "rX"


def test_preemption_signal_triggers_drain():
    """The training-side preemption flag (set by SIGTERM via
    install_preemption_handler) also stops serving admission and drains."""
    cfg = ServingConfig(max_batch_size=1, batch_window_s=0.0)
    srv = InferenceServer(object(), cfg, generate_fn=echo_gen())
    try:
        assert srv.submit(np.arange(3)).result(5) is not None
        fault._PREEMPTION["requested"] = True
        with pytest.raises(ServerDrainingError):
            srv.submit(np.arange(3))
        assert srv._drained.wait(5)  # worker noticed and drained by itself
    finally:
        fault._PREEMPTION["requested"] = False
        srv.close()


@pytest.mark.skipif(sys.platform == "win32", reason="POSIX signals")
def test_sigterm_drain_exits_143_without_dropping_inflight(tmp_path):
    """Real SIGTERM against a serving process: exit code 143, the in-flight
    batch replies, queued requests get retriable rejections — zero futures
    left unresolved."""
    script = r"""
import atexit, sys, time, threading
import numpy as np
from accelerate_tpu.serving import InferenceServer, install_drain_handler
from accelerate_tpu.utils.dataclasses import ServingConfig

def gen(model, ids, max_new_tokens=4, **kw):
    time.sleep(0.4)  # the SIGTERM lands while this batch is in flight
    return np.concatenate([ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1)

srv = InferenceServer(
    object(),
    ServingConfig(max_batch_size=1, batch_window_s=0.0, max_queue=64),
    generate_fn=gen,
)
assert install_drain_handler(srv)
futs = [srv.submit(np.arange(4)) for _ in range(5)]

@atexit.register
def report():
    done = sum(1 for f in futs if f.done())
    ok = sum(1 for f in futs if f.done() and f.exception() is None)
    print(f"RESULT done={done} ok={ok}", flush=True)

print("READY", flush=True)
time.sleep(30)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.1)  # first batch is mid-flight
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 143, f"exit={proc.returncode}\n{out}\n{err}"
    result = [l for l in out.splitlines() if l.startswith("RESULT")]
    assert result, f"no RESULT line:\n{out}\n{err}"
    fields = dict(kv.split("=") for kv in result[0].split()[1:])
    assert fields["done"] == "5"  # every future resolved — none dropped
    assert int(fields["ok"]) >= 1  # the in-flight batch replied


# ------------------------------------------------------------ fault injection
def test_fault_injected_batch_death_loses_and_duplicates_nothing(fault_inject):
    """A batch killed mid-flight (injected ``serving_before_batch:raise``)
    retries once the injection is disarmed; every request resolves exactly
    once with its own row."""
    batches = []
    cfg = ServingConfig(
        max_retries=50, retry_backoff_s=0.01, retry_backoff_max_s=0.02,
        breaker_threshold=100, max_batch_size=4, batch_window_s=0.05,
    )
    srv = InferenceServer(object(), cfg, generate_fn=echo_gen(batches))
    try:
        fault_inject("serving_before_batch:raise")
        futs = [srv.submit(np.full(4, i, dtype=np.int32), max_new_tokens=2)
                for i in range(3)]
        assert wait_until(lambda: srv.metrics["batch_failures"] >= 2)
        assert not any(f.done() for f in futs)  # failing, not failed
        os.environ.pop(fault.FAULT_INJECT_ENV, None)  # "backend recovers"
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(5).tokens, np.full(6, i, dtype=np.int32)
            )
        assert srv.metrics["completed"] == 3  # exactly once each
        assert len(batches) == 1  # ONE successful execution, no replays
    finally:
        srv.close()


def test_reply_fault_fails_batch_and_server_keeps_serving(fault_inject):
    """A failure AFTER the batch executed (armed ``serving_before_reply``)
    fails that batch's futures with BatchExecutionError instead of killing
    the worker with the results stranded — and the server keeps serving."""
    cfg = ServingConfig(max_batch_size=2, batch_window_s=0.0, max_retries=0)
    srv = InferenceServer(object(), cfg, generate_fn=echo_gen())
    try:
        fault_inject("serving_before_reply:raise")
        f = srv.submit(np.arange(3))
        with pytest.raises(BatchExecutionError) as exc_info:
            f.result(5)
        assert isinstance(exc_info.value.__cause__, fault.FaultInjected)
        os.environ.pop(fault.FAULT_INJECT_ENV, None)
        # the reply-stage failure cost one batch, not the worker
        assert srv.submit(np.arange(3)).result(5).tokens is not None
    finally:
        srv.close()


# ---------------------------------------------------------------- seed keying
def test_sampled_requests_batch_only_with_matching_seed():
    """Sampled traffic keys batching on seed (a request's draws must come
    from ITS seed); greedy traffic ignores seed and coalesces freely."""
    gate = threading.Event()
    recorded = []

    def fn(model, ids, max_new_tokens=4, seed=0, **kw):
        if not gate.is_set():
            gate.wait(10)
        recorded.append((ids.shape[0], seed))
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(max_batch_size=4, batch_window_s=0.0, batch_bucket=False)
    srv = InferenceServer(object(), cfg, generate_fn=fn)
    try:
        blocker = srv.submit(np.arange(4))
        assert wait_until(lambda: srv.queue_depth() == 0)
        futs = [
            srv.submit(np.arange(4), temperature=0.7, seed=1),
            srv.submit(np.arange(4), temperature=0.7, seed=1),
            srv.submit(np.arange(4), temperature=0.7, seed=2),
            srv.submit(np.arange(4), seed=5),  # greedy: seed is irrelevant
            srv.submit(np.arange(4), seed=6),
        ]
        gate.set()
        blocker.result(5)
        [f.result(5) for f in futs]
    finally:
        gate.set()
        srv.close()
    # after the blocker: the seed-1 pair shares a batch, seed 2 rides alone,
    # the two greedy requests coalesce despite different seeds
    assert recorded[1:] == [(2, 1), (1, 2), (2, 5)]


# ------------------------------------------------------------- degradation
def test_pressure_clamps_token_budget_before_shedding():
    gate = threading.Event()
    batches = []

    def gated(model, ids, max_new_tokens=4, **kw):
        if gate.is_set():
            batches.append((ids.shape, max_new_tokens))
        else:
            gate.wait(10)
        return np.concatenate(
            [ids, np.ones((ids.shape[0], max_new_tokens), np.int32)], axis=1
        )

    cfg = ServingConfig(
        max_queue=10, degrade_queue_fraction=0.5, degrade_hard_fraction=0.9,
        degraded_max_new_tokens=4, max_batch_size=8, batch_window_s=0.0,
    )
    srv = InferenceServer(object(), cfg, generate_fn=gated)
    try:
        blocker = srv.submit(np.arange(3), max_new_tokens=32)
        assert wait_until(lambda: srv.queue_depth() == 0)
        futs = [srv.submit(np.arange(3), max_new_tokens=32) for _ in range(6)]
        gate.set()
        results = [f.result(5) for f in futs]
        blocker.result(5)
        # queue sat above the 50% watermark: budgets were clamped to 4
        assert any(budget == 4 for _, budget in batches)
        assert any(r.degraded for r in results)
        assert srv.metrics["degraded"] > 0
        # nothing was shed or rejected — degradation came first
        assert srv.metrics["shed_deadline"] == 0
        assert srv.metrics["rejected_queue_full"] == 0
        assert srv.metrics["completed"] == 7
    finally:
        gate.set()
        srv.close()


# ------------------------------------------------------------------- metrics
class _CollectingTracker:
    name = "collect"

    def __init__(self):
        self.entries = []

    def log_batch(self, entries):
        self.entries.extend(entries)


def test_metrics_flow_through_tracker_log_batch():
    tracker = _CollectingTracker()
    with InferenceServer(
        object(), ServingConfig(), generate_fn=echo_gen(), trackers=[tracker]
    ) as srv:
        srv.submit(np.arange(3), max_new_tokens=2).result(5)
        snapshot = srv.log_metrics(step=7)
    assert snapshot["serving/completed"] == 1
    assert snapshot["serving/latency_p50"] is not None
    assert snapshot["serving/latency_p99"] >= snapshot["serving/latency_p50"]
    values, step, _ = tracker.entries[-1]
    # close() force-flushes a final snapshot after log_metrics' explicit one
    explicit = [e for e in tracker.entries if e[1] == 7]
    assert explicit and explicit[0][0]["serving/completed"] == 1
    assert "serving/queue_depth" in values
    assert "serving/breaker_state" in values


def test_latency_reservoir_percentiles_bounded_memory():
    r = LatencyReservoir(size=100)
    for v in range(1000):
        r.add(float(v))
    assert r.count == 1000
    # window holds the last 100 samples: 900..999
    assert r.percentile(50) == pytest.approx(950, abs=2)
    assert r.percentile(99) == pytest.approx(998, abs=2)
    snap = r.snapshot(prefix="x_")
    assert snap["x_count"] == 1000 and snap["x_max"] == 999.0
    assert LatencyReservoir().percentile(50) is None


# ---------------------------------------------------------------- validation
def test_submit_validates_shapes():
    with InferenceServer(object(), ServingConfig(), generate_fn=echo_gen()) as srv:
        with pytest.raises(ValueError):
            srv.submit(np.zeros((2, 4), np.int32))  # two rows
        with pytest.raises(ValueError):
            srv.submit(np.zeros((0,), np.int32))  # empty prompt
        # a (1, L) prompt is accepted (the common HF shape)
        assert srv.submit(np.zeros((1, 4), np.int32)).result(5) is not None


def test_serving_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(max_queue=0)
    with pytest.raises(ValueError):
        ServingConfig(max_retries=-1)
    with pytest.raises(ValueError):
        ServingConfig(retry_backoff_s=1.0, retry_backoff_max_s=0.5)
    with pytest.raises(ValueError):
        ServingConfig(breaker_threshold=0)
    with pytest.raises(ValueError):
        ServingConfig(degrade_queue_fraction=0.9, degrade_hard_fraction=0.5)
    with pytest.raises(ValueError):
        ServingConfig(batch_window_s=-1)


# ------------------------------------------------------------- real model e2e
def test_real_model_end_to_end_matches_direct_generate():
    """Two concurrent requests batch into ONE real compiled generate() and
    each row matches a direct generate() of the stacked batch (greedy is
    deterministic, same program via the per-model LRU)."""
    from accelerate_tpu.inference import generate, generate_cache_stats
    from accelerate_tpu.models.llama import LlamaConfig, create_llama

    import jax.numpy as jnp

    cfg_model = LlamaConfig.tiny(compute_dtype=jnp.float32)
    model = create_llama(cfg_model, seed=0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg_model.vocab_size, size=(2, 6)).astype(np.int32)

    cfg = ServingConfig(
        max_batch_size=2, batch_window_s=0.5, pad_total_multiple=16,
        batch_bucket=True,
    )
    with InferenceServer(model, cfg) as srv:
        futs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        rows = [f.result(60).tokens for f in futs]
    direct = np.asarray(generate(model, prompts, max_new_tokens=4, pad_to=16))
    np.testing.assert_array_equal(np.stack(rows), direct)
    assert srv.metrics["batches"] == 1  # they shared one execution
    # the serving path reused the LRU (bucketed shapes, bounded programs)
    assert generate_cache_stats(model)["size"] <= 2
