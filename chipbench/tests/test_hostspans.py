"""The readers of the program's spans: the seven per-layer metrics on hand-made
session spans, and ``hostspans`` on a real CPU profile and on synthetic gaps."""

import os
import types

import pytest

from chipbench import hostspans, lib, trace

S = "gpt2-large.serve-closed32"


def span(name, t0, t1, id, parent=0, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, id=id, parent=parent, attrs=attrs)


def decode(id, parent, t0, decoding, live, reserved, slots=4):
    return span("engine.decode_step", t0, t0 + 0.001, id, parent, decoding=decoding, slots=slots,
                kv_live_tokens=live, kv_reserved_tokens=reserved)


# two ticks of a server: 10 ms with a 7 ms and a 1 ms wait inside (one nested under
# another span), and 6 ms with a 5 ms wait; a readback outside any tick counts for none
SERVING = [
    span("serving.tick", 0.000, 0.010, 1, queue_depth=0, live=4),
    span("serving.admit", 0.0005, 0.0020, 2, 1),
    span("engine.prefill", 0.001, 0.002, 3, 2, prompt_len=100, bucket=512),
    decode(4, 1, 0.002, decoding=3, live=600, reserved=1000),
    span("engine.readback", 0.003, 0.010, 5, 1, kind="decode"),
    span("engine.readback", 0.0010, 0.0020, 6, 3, kind="prefill"),
    span("serving.tick", 0.010, 0.016, 7, queue_depth=0, live=4),
    span("engine.prefill_chunk", 0.010, 0.011, 8, 7, chunk_len=28, bucket=128),
    decode(9, 7, 0.0105, decoding=4, live=800, reserved=1000),
    span("engine.readback", 0.011, 0.016, 10, 7, kind="decode"),
    span("engine.readback", 0.020, 0.030, 11, 0, kind="decode"),
]
TRAINING = [
    span("train.data_wait", 0.000, 0.001, 1, step=0),
    span("train.step", 0.001, 0.003, 2, step=0),
    span("train.data_wait", 0.100, 0.102, 3, step=1),
    span("train.step", 0.102, 0.103, 4, step=1),
    span("train.data_wait", 0.200, 0.201, 5, step=2),
    span("train.step", 0.201, 0.205, 6, step=2),
    span("train.ring_drain", 0.390, 0.400, 7),
]
EXPECTED = {
    "tick_host_ms_p50.serve": (SERVING, (2.0 + 1.0) / 2),
    "decode_fill.serve": (SERVING, 100 * (3 / 4 + 4 / 4) / 2),
    "prefill_padding.serve": (SERVING, 100 * (1 - 128 / 640)),
    "kv_live_of_reserved.serve": (SERVING, 100 * (0.6 + 0.8) / 2),
    "data_wait_share.train": (TRAINING, 100 * 0.004 / 0.400),
}


@pytest.fixture
def session(monkeypatch):
    """Puts a hand-made list in the place of the tracer's."""

    def put(spans):
        monkeypatch.setattr(
            hostspans, "session_spans",
            lambda name=None: None if spans is None
            else [sp for sp in spans if name in (None, sp.name)])

    return put


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_hand_made_session_spans(metric, session):
    spans, expected = EXPECTED[metric]
    reader = lib.load_module("metrics", metric)
    session(spans)
    assert reader.read(None) == pytest.approx(expected)
    # the other cell's spans, an empty session, and a program without the list
    for nothing in (TRAINING if spans is SERVING else SERVING, [], None):
        session(nothing)
        assert reader.read(None) is None


def test_sampler_reader_matches_the_kernel_by_its_name():
    reader = lib.load_module("metrics", "sampler_ms_p50.serve")
    ev = trace.Event
    named = '%fused_sample.3 = s32[32]{0} custom-call(f32[32,50257]{1,0} %x), custom_call_target="tpu_custom_call"'
    other = '%closed_call.9 = f32[8]{0} custom-call(f32[8]{0} %fused_sample.3), custom_call_target="tpu_custom_call"'
    summary = trace.summarize([trace.DeviceTrace(
        ops=[ev(named, 0, 400_000), ev(other, 500_000, 100_000), ev(named, 1_000_000, 600_000)],
        modules=[])])
    assert reader.read(types.SimpleNamespace(summary=summary)) == pytest.approx(0.5)
    unnamed = trace.summarize([trace.DeviceTrace(ops=[ev(other, 0, 100)], modules=[])])
    assert reader.read(types.SimpleNamespace(summary=unnamed)) is None


def test_session_spans_come_from_the_programs_tracer(monkeypatch):
    from accelerate_tpu import tracing

    kept = [span("a", 0, 1, 1), span("b", 1, 2, 2)]
    tracer = types.SimpleNamespace(
        session_dropped=0,
        session_spans=lambda name=None: [sp for sp in kept if name in (None, sp.name)])
    monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)
    assert [sp.name for sp in hostspans.session_spans()] == ["a", "b"]
    assert [sp.name for sp in hostspans.session_spans("b")] == ["b"]
    tracer.session_dropped = 3  # a partial session is no session
    assert hostspans.session_spans() is None
    monkeypatch.setattr(tracing, "get_tracer", lambda: types.SimpleNamespace())  # before the bridge
    assert hostspans.session_spans() is None


def test_name_gaps_on_synthetic_gaps():
    hs = hostspans.HostSpan
    spans = [
        hs("serving.tick", 100, 900, "python#0", {}),
        hs("engine.readback", 300, 800, "python#0", {}),
        hs("client.send", 200, 1500, "python#1", {}),
        hs("serving.tick", 2000, 2600, "python#0", {}),
    ]
    gaps = [(5e-7, 400), (4e-7, 850), (3e-7, 1700), (2e-7, 50), (1e-7, 2600)]
    assert hostspans.name_gaps(gaps, spans) == [
        ["engine.readback", 5e-7],      # the innermost of three that cover it
        ["client.send", 4e-7],          # of two, the one opened last
        ["after:client.send", 3e-7],    # none covers it: the one that closed last
        ["no_span", 2e-7],              # before the first
        ["after:serving.tick", 1e-7],   # a span's end is not inside it
    ]
    assert hostspans.name_gaps(gaps[:1], []) == [["no_span", 5e-7]]


def test_read_finds_the_programs_spans_in_a_real_profile(tmp_path):
    import time

    import jax

    from accelerate_tpu import tracing
    from chipbench import run

    run.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("not.the.programs"):  # no ids: left out
            pass
        with tracing.span("serving.tick", queue_depth=2, live=4) as tick:
            with tracing.span("engine.readback", kind="decode", popped=1) as wait:
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    spans = hostspans.read(str(tmp_path))
    assert [s.name for s in spans] == ["serving.tick", "engine.readback"]
    outer, inner = spans
    assert outer.stats["span"] == tick.id and outer.stats["queue_depth"] == 2
    assert inner.stats["parent"] == tick.id and inner.stats["kind"] == "decode"
    assert outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns
    assert outer.thread == inner.thread
    assert (inner.end_ns - inner.start_ns) / 1e9 == pytest.approx(wait.t1 - wait.t0, abs=2e-3)
    # a gap that starts while the host waits is named by the wait
    middle = (inner.start_ns + inner.end_ns) / 2
    assert hostspans.name_gaps([(1e-3, middle)], spans) == [["engine.readback", 1e-3]]
    # and the tracer kept the same two for the readers in process
    assert [sp.name for sp in hostspans.session_spans()] == ["serving.tick", "engine.readback"]
    assert os.path.isfile(trace.find_xplane(str(tmp_path)))
