"""Flight recorder & request tracing suite (docs/observability.md):

* trace propagation — a failed-over request is ONE trace: both dispatch
  spans (dead replica + survivor) and the typed failover decision with
  its ``__cause__``-chained error event share the fleet-minted trace ID,
  and the result carries ``failover_count``;
* flight dumps — a serving worker death auto-dumps the retained window
  as Chrome-trace JSON (the batch span carries the SystemExit error
  event); the dump budget (``max_dumps``) is enforced;
* ring discipline — bounded per-thread rings drop oldest-first with an
  exact ``dropped_spans`` count; disabled tracing hands back ONE shared
  no-op context manager (no per-call allocation);
* the profiler bridge — a span opened inside a real ``jax.profiler``
  session is in the ``.xplane.pb``'s ``/host:CPU`` plane with its
  attributes, id and parent; the session's spans are kept whole; with no
  session no annotation is made; every step leaves a span (no sampling);
  a continuous server's tick nests dispatch, readback and reply;
* latency surface — ``ServingResult`` reports queue_wait_s / prefill_s /
  decode_steps for every completed request;
* MetricsRegistry — the unified counters/gauges/reservoir surface and
  the single periodic tracker flush (due/flush/maybe_flush).
"""

import contextlib
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from accelerate_tpu import tracing
from accelerate_tpu.fleet import FleetRouter
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.tracing import MetricsRegistry, Tracer
from accelerate_tpu.utils.dataclasses import (
    FleetConfig,
    ServingConfig,
    TracingConfig,
)
from accelerate_tpu.utils.fault import ServingError

PROMPT = np.arange(1, 6, dtype=np.int32)


def echo_gen(delay=0.0):
    def fn(model, ids, max_new_tokens=8, **kw):
        if delay:
            time.sleep(delay)
        new = np.repeat(ids[:, :1], max_new_tokens, axis=1)
        return np.concatenate([ids, new], axis=1)

    return fn


def killable_gen(kill_event, delay=0.005):
    def fn(model, ids, max_new_tokens=8, **kw):
        if kill_event.is_set():
            kill_event.clear()
            raise SystemExit(1)
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


def make_server(gen_fn, replica_id=None, **cfg_kw):
    cfg_kw.setdefault("max_queue", 128)
    cfg_kw.setdefault("max_batch_size", 4)
    cfg_kw.setdefault("batch_window_s", 0.001)
    cfg_kw.setdefault("max_retries", 0)
    cfg = ServingConfig(**cfg_kw)
    return InferenceServer(object(), cfg, generate_fn=gen_fn, replica_id=replica_id)


@pytest.fixture
def tracer(tmp_path):
    """A fresh enabled default tracer dumping into tmp_path; the previous
    default config (the session-wide tmp dump dir from conftest) is
    restored afterwards so other suites keep their usual tracer."""
    prev_cfg = tracing.get_tracer().config
    t = tracing.configure(TracingConfig(
        enabled=True, ring_capacity=4096, retain_s=60.0,
        dump_dir=str(tmp_path), max_dumps=4,
    ))
    yield t
    tracing.configure(prev_cfg)


# -------------------------------------------------------- trace propagation
def test_failover_is_one_trace_with_both_dispatches(tracer):
    """Kill r0 mid-batch: the affected request's trace must contain the
    dispatch to the dead replica, the typed failover decision (with the
    error recorded as a span event), and the re-dispatch to a survivor."""
    kill = threading.Event()
    servers = {
        "r0": make_server(killable_gen(kill), replica_id="r0"),
        "r1": make_server(echo_gen(), replica_id="r1"),
    }
    router = FleetRouter(servers, FleetConfig(probe_interval_s=0.05))
    try:
        kill.set()
        futs = [router.submit(PROMPT, max_new_tokens=2) for _ in range(6)]
        results = [f.result(timeout=10) for f in futs]
        assert wait_until(lambda: router.metrics["failovers"] >= 1)
    finally:
        router.close(drain=False)

    failover_spans = tracer.spans(name="fleet.failover")
    assert failover_spans, "no failover decision span recorded"
    sp = failover_spans[0]
    assert sp.trace_id is not None
    assert sp.attrs["outcome"] == "resubmitted"
    # the typed error event: taxonomy attributes, never prose
    events = {name: attrs for _, name, attrs in sp.events}
    assert "error" in events
    assert events["error"]["type"]  # e.g. ReplicaDeadError
    assert events["error"]["retriable"] is True
    assert "cause" in events["error"]  # the __cause__ chain is surfaced

    # ONE trace, two dispatch spans, two distinct replicas
    dispatches = tracer.spans(trace_id=sp.trace_id, name="fleet.dispatch")
    assert len(dispatches) >= 2
    assert len({d.attrs["replica"] for d in dispatches}) >= 2
    # the whole submit is under the same trace
    assert tracer.spans(trace_id=sp.trace_id, name="fleet.submit")
    # and the client-visible result reports the hop count
    failed_over = [r for r in results if r.failover_count >= 1]
    assert failed_over and all(r.replica_id == "r1" for r in failed_over)


def test_trace_id_threads_submit_to_batch(tracer):
    srv = make_server(echo_gen())
    try:
        fut = srv.submit(PROMPT, max_new_tokens=2)
        fut.result(timeout=10)
    finally:
        srv.close()
    tids = {s.trace_id for s in tracer.spans(name="serving.batch")}
    assert None not in tids and len(tids) == 1


# -------------------------------------------------------------- flight dump
def test_worker_death_dumps_flight_recording(tracer, tmp_path):
    kill = threading.Event()
    srv = make_server(killable_gen(kill))
    try:
        kill.set()
        fut = srv.submit(PROMPT, max_new_tokens=2)
        with pytest.raises(ServingError):
            fut.result(timeout=10)
        assert wait_until(lambda: any(
            fn.startswith("flight-worker_death-") for fn in os.listdir(tmp_path)
        ))
    finally:
        srv.close()
    path = next(
        tmp_path / fn for fn in os.listdir(tmp_path)
        if fn.startswith("flight-worker_death-")
    )
    doc = json.loads(path.read_text())
    assert doc["otherData"]["reason"] == "worker_death"
    batch = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "serving.batch"]
    assert batch and batch[0]["args"]["trace_id"]
    errors = [e for e in doc["traceEvents"]
              if e["ph"] == "i" and e["name"] == "error"]
    assert any(e["args"]["type"] == "SystemExit" for e in errors)


def test_dump_budget_is_bounded(tracer, tmp_path):
    with tracer.span("x"):
        pass
    paths = [tracer.dump("budget") for _ in range(10)]
    written = [p for p in paths if p is not None]
    assert len(written) == tracer.config.max_dumps
    assert all(os.path.exists(p) for p in written)


def test_disabled_tracer_never_dumps(tmp_path):
    t = Tracer(TracingConfig(enabled=False, dump_dir=str(tmp_path)))
    assert t.dump("nope") is None and t.maybe_dump("nope") is None
    assert os.listdir(tmp_path) == []


# ----------------------------------------------------------- ring discipline
def test_ring_drops_oldest_and_counts():
    t = Tracer(TracingConfig(enabled=True, ring_capacity=16))
    for i in range(40):
        with t.span("s", None, i=i):
            pass
    assert t.dropped_spans() == 24
    kept = t.spans(name="s")
    assert len(kept) == 16
    # drop-oldest: the survivors are exactly the 16 newest
    assert {s.attrs["i"] for s in kept} == set(range(24, 40))


def test_disabled_span_is_shared_noop():
    t = Tracer(TracingConfig(enabled=False))
    cms = {id(t.span("a")), id(t.span("b", "tid", k=1))}
    assert len(cms) == 1  # ONE shared object: no per-call allocation
    with t.span("a") as sp:
        sp.set("k", 1)  # no-op, no error
        sp.event("e")
    assert t.spans() == [] and t.dropped_spans() == 0


def test_every_step_leaves_a_span(tracer):
    """No sampling: eight steps leave eight spans, each with its own id."""
    for step in range(8):
        with tracing.span("hot", step=step):
            pass
    recorded = tracing.get_tracer().spans(name="hot")
    assert [sp.attrs["step"] for sp in recorded] == list(range(8))
    assert len({sp.id for sp in recorded}) == 8
    assert not hasattr(tracing, "step_span") and not hasattr(tracing, "epoch")
    assert not hasattr(TracingConfig(), "decode_sample_every")


def test_span_records_exception_as_typed_event(tracer):
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("nope")
    sp = tracer.spans(name="boom")[0]
    events = {name: attrs for _, name, attrs in sp.events}
    assert events["error"]["type"] == "ValueError"


# ----------------------------------------------------------- result surface
def test_serving_result_carries_latency_breakdown(tracer):
    srv = make_server(echo_gen(delay=0.01))
    try:
        res = srv.submit(PROMPT, max_new_tokens=3).result(timeout=10)
    finally:
        srv.close()
    assert res.queue_wait_s is not None and res.queue_wait_s >= 0.0
    assert res.decode_steps == 3
    assert res.failover_count == 0


# --------------------------------------------------------- metrics registry
class _FakeTracker:
    name = "fake"

    def __init__(self):
        self.batches = []

    def log_batch(self, entries):
        self.batches.append(entries)


def test_registry_counters_gauges_snapshot():
    reg = MetricsRegistry(prefix="t/", counters=("a",))
    reg.bump("a")
    reg.bump("a", 2)
    reg.gauge("g", 1.5)
    assert reg["a"] == 3 and reg["g"] == 1.5
    snap = reg.snapshot()
    assert snap == {"t/a": 3, "t/g": 1.5}


def test_registry_ingest_flattens_nested_stats():
    reg = MetricsRegistry(prefix="serving/")
    reg.ingest({"kv": {"hbm_bytes": 42, "blocks": {"free": 7}},
                "live": 3, "note": "ignored-not-numeric"}, prefix="engine")
    snap = reg.snapshot()
    assert snap["serving/engine/kv/hbm_bytes"] == 42
    assert snap["serving/engine/kv/blocks/free"] == 7
    assert snap["serving/engine/live"] == 3
    assert "serving/engine/note" not in snap


def test_registry_observe_expands_percentiles():
    reg = MetricsRegistry(prefix="t/")
    for v in range(100):
        reg.observe("lat", v / 100.0)
    snap = reg.snapshot()
    assert any(k.startswith("t/lat_") for k in snap)


def test_registry_flush_is_the_single_periodic_path():
    clock = [100.0]
    reg = MetricsRegistry(prefix="t/", counters=("a",), clock=lambda: clock[0])
    tracker = _FakeTracker()
    assert not reg.due(5.0)  # just constructed
    assert reg.maybe_flush([tracker], 5.0) is False
    clock[0] += 6.0
    assert reg.due(5.0)
    assert reg.maybe_flush([tracker], 5.0, step=7) is True
    assert len(tracker.batches) == 1
    (values, step, _kw), = tracker.batches[0]
    assert step == 7 and "t/a" in values
    # the flush reset the interval
    assert not reg.due(5.0)
    assert reg.due(None) is False  # None interval: never due


def test_serving_and_fleet_share_registry_flush(tracer):
    """Both periodic flushes route through MetricsRegistry.maybe_flush —
    the serving worker and the fleet prober each push their own snapshot
    to trackers, outside their respective locks."""
    tracker = _FakeTracker()
    srv = make_server(echo_gen(), metrics_interval_s=0.05)
    srv.trackers = [tracker]
    try:
        srv.submit(PROMPT, max_new_tokens=2).result(timeout=10)
        assert wait_until(lambda: any(
            any(k.startswith("serving/") for k in values)
            for batch in tracker.batches for values, _s, _kw in batch
        ))
    finally:
        srv.close()

    fleet_tracker = _FakeTracker()
    router = FleetRouter(
        {"r0": make_server(echo_gen(), replica_id="r0")},
        FleetConfig(probe_interval_s=0.02, metrics_interval_s=0.05),
        trackers=[fleet_tracker],
    )
    try:
        router.submit(PROMPT, max_new_tokens=2).result(timeout=10)
        assert wait_until(lambda: any(
            any(k.startswith("fleet/") for k in values)
            for batch in fleet_tracker.batches for values, _s, _kw in batch
        ))
    finally:
        router.close(drain=False)


# --------------------------------------------------------- profiler bridge
@contextlib.contextmanager
def profiler_session(directory):
    """A real ``jax.profiler`` session on the CPU under the options
    ``chipbench/run.py:start_trace`` sets."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(directory):
    """``{name: [(start_ns, end_ns, stats)]}`` of the ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                             recursive=True))
    assert found, "the profiler wrote no .xplane.pb"
    out = {}
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                )
    return out


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is made."""

    active = False
    made = 0

    def __init__(self, name, **stats):
        type(self).made += 1

    @classmethod
    def is_enabled(cls):
        return cls.active

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    _FakeAnnotation.active, _FakeAnnotation.made = False, 0
    monkeypatch.setattr(tracing, "_ANNOTATION", _FakeAnnotation)
    return _FakeAnnotation


def test_parent_and_id_follow_the_threads_open_span(tracer):
    with tracing.span("outer") as outer:
        with tracing.span("mid") as mid:
            with tracing.span("leaf") as leaf:
                pass
        with tracing.span("second") as second:
            pass
    seen = []

    def other_thread():
        with tracing.span("elsewhere") as sp:
            seen.append(sp)

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    assert outer.parent == 0 and mid.parent == outer.id
    assert leaf.parent == mid.id and second.parent == outer.id
    assert seen[0].parent == 0  # the stack is the thread's own
    assert len({outer.id, mid.id, leaf.id, second.id, seen[0].id}) == 5
    args = {e["name"]: e["args"] for e in tracer.to_chrome_trace()["traceEvents"]
            if e["ph"] == "X"}
    assert args["leaf"]["span"] == leaf.id and args["leaf"]["parent"] == mid.id


def test_span_in_a_profiler_session_is_in_the_host_plane(tracer, tmp_path):
    with profiler_session(tmp_path):
        with tracing.span("bridge.outer", trace_id="t-1", live=3, share=0.5,
                          kind="decode", skipped=[1, 2]) as outer:
            time.sleep(0.002)
            with tracing.span("bridge.inner", popped=2) as inner:
                time.sleep(0.002)
            time.sleep(0.002)
    assert outer.profiled and inner.profiled
    events = host_events(tmp_path)
    (o0, o1, ostats), = events["bridge.outer"]
    (i0, i1, istats), = events["bridge.inner"]
    assert ostats["live"] == 3 and ostats["kind"] == "decode"
    assert ostats["share"] == pytest.approx(0.5)
    assert "skipped" not in ostats  # scalars only
    assert ostats["trace_id"] == "t-1"
    assert ostats["span"] == outer.id and ostats["parent"] == 0
    assert istats["span"] == inner.id and istats["parent"] == outer.id
    assert istats["popped"] == 2
    assert o0 <= i0 and i1 <= o1  # the parent's annotation encloses the child's
    # on the profiler's clock the annotation is as long as the span
    assert (o1 - o0) / 1e9 == pytest.approx(outer.duration_s, abs=2e-3)


def test_session_spans_are_the_sessions_own(tracer, tmp_path):
    with tracing.span("before"):
        pass
    with tracing.span("straddles"):  # opened outside: no annotation, not kept
        with profiler_session(tmp_path / "one"):
            for i in range(3):
                with tracing.span("inside", i=i):
                    pass
    with tracing.span("between"):
        pass
    first = tracer.session_spans()
    assert [sp.name for sp in first] == ["inside"] * 3
    assert [sp.attrs["i"] for sp in first] == [0, 1, 2]  # oldest first
    assert all(sp.profiled for sp in first)
    assert not any(sp.profiled for sp in tracer.spans() if sp.name != "inside")
    assert tracer.session_spans(name="nothing") == []
    # kept after the session, whatever runs afterwards
    for _ in range(20):
        with tracing.span("after"):
            pass
    assert len(tracer.session_spans()) == 3
    # the first span of a later session empties the list
    with profiler_session(tmp_path / "two"):
        with tracing.span("later"):
            pass
    assert [sp.name for sp in tracer.session_spans()] == ["later"]
    assert tracer.session_dropped == 0


def test_session_store_counts_what_it_drops(tracer, fake_annotation, monkeypatch):
    monkeypatch.setattr(tracing, "SESSION_CAPACITY", 8)
    fake_annotation.active = True
    for i in range(11):
        with tracing.span("s", i=i):
            pass
    kept = tracer.session_spans()
    assert [sp.attrs["i"] for sp in kept] == list(range(8))
    assert tracer.session_dropped == 3
    fake_annotation.active = False
    with tracing.span("outside"):
        pass
    fake_annotation.active = True
    with tracing.span("next"):
        pass
    assert [sp.name for sp in tracer.session_spans()] == ["next"]
    assert tracer.session_dropped == 0  # a new session counts anew


@pytest.mark.parametrize("session", [False, True])
def test_annotations_are_made_only_inside_a_session(tracer, fake_annotation, session):
    fake_annotation.active = session
    for _ in range(5):
        with tracing.span("s", k=1) as sp:
            pass
    assert fake_annotation.made == (5 if session else 0)
    assert sp.profiled is session
    assert len(tracer.session_spans()) == (5 if session else 0)


def test_a_profiler_that_fails_does_not_break_span(tracer, monkeypatch):
    class Broken:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name, **stats):
            raise RuntimeError("no profiler here")

    monkeypatch.setattr(tracing, "_ANNOTATION", Broken)
    with tracing.span("s") as sp:
        sp.set("k", 1)
    assert tracer.spans(name="s")[0].attrs == {"k": 1}


def test_disabled_tracer_is_shared_noop_inside_a_session(fake_annotation):
    fake_annotation.active = True
    t = Tracer(TracingConfig(enabled=False))
    assert t.span("a") is t.span("b", "tid", k=1)
    with t.span("a"):
        pass
    assert fake_annotation.made == 0 and t.session_spans() == []


def test_continuous_server_tick_nests_dispatch_readback_and_reply(tracer):
    """A tiny continuous server: every decode step has its span, and the
    engine's spans hang under ``serving.tick`` by the parent field."""
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaConfig, create_llama

    model = create_llama(LlamaConfig.tiny(compute_dtype=jnp.float32), seed=0)
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=32,
        engine_prompt_bucket=8, engine_readback_lag=1,
    )
    prompts = [[5, 9, 3], [12, 7, 4, 10, 6], [8, 1]]
    with InferenceServer(model, cfg) as srv:
        futs = [srv.submit(p, max_new_tokens=4, pad_token_id=0) for p in prompts]
        for f in futs:
            f.result(timeout=120)
        steps = srv.engine.steps
    by_id = {sp.id: sp for sp in tracer.spans()}
    named = lambda name: [sp for sp in by_id.values() if sp.name == name]

    def ancestors(sp):
        while sp.parent:
            sp = by_id[sp.parent]
            yield sp.name

    ticks = named("serving.tick")
    assert ticks and all(sp.parent == 0 for sp in ticks)
    assert {"queue_depth", "live"} <= set(ticks[0].attrs)
    assert sum(sp.attrs.get("admitted", 0) for sp in ticks) == len(prompts)
    decode = named("engine.decode_step")
    assert len(decode) == steps  # one span a step: nothing is sampled away
    for sp in decode:
        assert by_id[sp.parent].name == "serving.tick"
        assert 1 <= sp.attrs["decoding"] <= sp.attrs["slots"] == 2
        assert 0 < sp.attrs["kv_live_tokens"] <= sp.attrs["kv_reserved_tokens"]
        assert sp.attrs["kv_walked_tokens"] == 0  # a dense cache: no paged kernel walks it
    prefill = named("engine.prefill")
    assert len(prefill) == len(prompts)
    assert sorted(sp.attrs["prompt_len"] for sp in prefill) == [2, 3, 5]
    for sp in prefill:
        assert sp.attrs["bucket"] == 8
        assert list(ancestors(sp)) == ["serving.admit", "serving.tick"]
    readback = named("engine.readback")
    assert {sp.attrs["kind"] for sp in readback} == {"prefill", "decode"}
    assert all(by_id[sp.parent].name == "serving.tick" for sp in readback)
    reply = named("serving.reply")
    assert sum(sp.attrs["retired"] for sp in reply) == len(prompts)
    assert all(by_id[sp.parent].name == "serving.tick" for sp in reply)
    assert all("serving.tick" in ancestors(sp) for sp in named("engine.retire"))
    # a tick's own time is what is left of it once the waits are taken out
    for tick in ticks:
        inside = sum(sp.duration_s for sp in readback if sp.parent == tick.id)
        assert inside <= tick.duration_s + 1e-9


def test_train_step_and_data_wait_leave_a_span_every_step(tracer):
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.test_utils.training import (
        RegressionModel,
        make_regression_data,
        regression_loss,
    )

    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()
    try:
        acc = Accelerator()
        loader = acc.prepare_data_loader(
            make_regression_data(64), batch_size=16, drop_last=True
        )
        model, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
        step = acc.train_step(regression_loss, model=model, optimizer=opt)
        for batch in loader:
            step(batch)
    finally:
        for state in (AcceleratorState, GradientState, PartialState):
            state._reset_state()
    sent = tracer.spans(name="train.step")
    assert [sp.attrs["step"] for sp in sent] == [0, 1, 2, 3]
    assert all(sp.parent == 0 for sp in sent)
    waits = tracer.spans(name="train.data_wait")
    assert [sp.attrs["step"] for sp in waits][:4] == [0, 1, 2, 3]  # one a fetch


def test_train_plan_leaves_one_span_with_the_rung_and_the_bytes(tracer, monkeypatch):
    """One ``train.plan`` span a step built, with the rung kept, the rungs
    tried and the bytes, the same facts as ``step.plan``; every ``train.step``
    span names the rung."""
    import jax.numpy as jnp
    import optax

    import accelerate_tpu.accelerator as accelerator_module
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models.llama import REMAT_LADDER, LlamaConfig, create_llama, llama_loss
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    monkeypatch.setattr(accelerator_module, "_device_memory", lambda: (1 << 40, 12345))
    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()
    try:
        acc = Accelerator(gradient_accumulation_steps=2)
        model, opt = acc.prepare(create_llama(LlamaConfig.tiny(), seed=0), optax.sgd(0.1))
        step = acc.train_step(llama_loss)
        for _ in range(3):
            step({"input_ids": jnp.zeros((8, 16), jnp.int32)})
    finally:
        for state in (AcceleratorState, GradientState, PartialState):
            state._reset_state()
    (plan,) = tracer.spans(name="train.plan")
    assert plan.attrs == step.plan
    assert plan.attrs["remat"] == REMAT_LADDER[0] and plan.attrs["rungs_tried"] == 1
    assert plan.attrs["bytes_limit"] == 1 << 40 and plan.attrs["bytes_in_use"] == 12345
    assert plan.attrs["hbm_live"] > plan.attrs["accumulator_bytes"] > 0
    sent = tracer.spans(name="train.step")
    assert [sp.attrs["remat"] for sp in sent] == [REMAT_LADDER[0]] * 3
    assert plan.t1 <= sent[0].t0  # the plan is made before the first step is sent


# ------------------------------------------------- names in the device trace
def test_named_scopes_reach_the_lowered_programs(tracer):
    """A profile names device work by each operation's ``op_name``: the
    phases of the fused train step and of the engine's programs must be in
    the lowered text, and every Pallas kernel must carry its ``name=``."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.engine import ContinuousBatchingEngine
    from accelerate_tpu.models.llama import LlamaConfig, create_llama
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.test_utils.training import (
        RegressionModel,
        make_regression_data,
        regression_loss,
    )

    def lowered(jitted, *args):
        return jitted.lower(*args).as_text(debug_info=True)

    model = create_llama(LlamaConfig.tiny(compute_dtype=jnp.float32), seed=0)
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=32, prompt_bucket=8, kv_cache="paged",
        attention_impl="pallas",
    )
    text = lowered(eng._decode_jit, eng._donated, eng._carried, model.params,
                   eng._backend.device_tables())
    for name in ("kv.scatter", "engine.sample", "paged_decode", "fused_sample"):
        assert name in text, name
    dense = ContinuousBatchingEngine(model, slots=2, max_len=32, prompt_bucket=8,
                                     kv_cache="paged")
    text = lowered(dense._decode_jit, dense._donated, dense._carried, model.params,
                   dense._backend.device_tables())
    assert "kv.gather" in text and "kv.scatter" in text and "engine.sample" in text

    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()
    try:
        acc = Accelerator(gradient_accumulation_steps=2)
        net, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
        step = acc.train_step(regression_loss, model=net, optimizer=opt,
                              max_grad_norm=1.0)
        batch = {k: jnp.asarray(v[:16]) for k, v in make_regression_data(64).items()}
        text = step.lower(batch).as_text(debug_info=True)
    finally:
        for state in (AcceleratorState, GradientState, PartialState):
            state._reset_state()
    for name in ("train.forward_backward", "train.accumulate", "train.clip",
                 "train.optimizer"):
        assert name in text, name


def test_flash_kernels_carry_their_names():
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text(debug_info=True)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
