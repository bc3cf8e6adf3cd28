"""The readers of the host runtime beneath the program: ``gc_pause_share.*`` on
hand-made session spans, ``jax_compile_s.*`` on hand-made totals, both on the
program's own tracer after a real CPU profile, and each silent where its source
is missing (a program whose tracer keeps no totals spans no collection)."""

import gc
import types

import pytest

from chipbench import hostspans, lib

PAUSES = ("gc_pause_share.serve", "gc_pause_share.train")
COMPILES = ("jax_compile_s.serve", "jax_compile_s.train")


def span(name, t0, t1, id, parent=0, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, id=id, parent=parent, attrs=attrs)


# 0.400 s of session on two threads, 12 ms of it in the collector: 9 ms under a tick,
# 3 ms beside a wait on another thread (a collection stops every thread)
SESSION = [
    span("serving.tick", 0.000, 0.020, 1),
    span("python.gc", 0.005, 0.014, 2, 1, generation=2),
    span("engine.readback", 0.014, 0.020, 3, 1),
    span("client.wait", 0.100, 0.400, 4),
    span("python.gc", 0.200, 0.203, 5, 4, generation=1),
]
TOTALS = {"python.gc.gen0": {"count": 900, "seconds": 0.05, "max_s": 0.001},
          "python.gc.gen1": {"count": 80, "seconds": 0.04, "max_s": 0.003},
          "python.gc.gen2": {"count": 2, "seconds": 0.3, "max_s": 0.2},
          "jax.trace": {"count": 30, "seconds": 2.5, "max_s": 0.9},
          "jax.lower": {"count": 12, "seconds": 5.25, "max_s": 2.0},
          "jax.compile": {"count": 12, "seconds": 20.0, "max_s": 9.0}}


@pytest.fixture
def program(monkeypatch):
    """Puts a hand-made tracer in the place of the program's: ``put(spans,
    totals)``, with ``totals=False`` for a tracer that has none."""
    from accelerate_tpu import tracing

    def put(spans, totals=None):
        tracer = types.SimpleNamespace(
            session_dropped=0,
            session_spans=lambda name=None: [sp for sp in spans if name in (None, sp.name)])
        if totals is not False:
            tracer.totals = lambda at_session_start=False: totals
        monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)

    return put


@pytest.mark.parametrize("metric", PAUSES)
def test_gc_pause_share_on_hand_made_session_spans(metric, program):
    reader = lib.load_module("metrics", metric)
    program(SESSION, TOTALS)
    assert reader.read(None) == pytest.approx(100 * 0.012 / 0.400)
    program([sp for sp in SESSION if sp.name != "python.gc"], TOTALS)
    assert reader.read(None) == 0.0  # no collection in the window: a reading, not silence
    program(SESSION, totals=False)  # a program before the hooks
    assert reader.read(None) is None
    program([], TOTALS)
    assert reader.read(None) is None


@pytest.mark.parametrize("metric", COMPILES)
def test_jax_compile_s_on_hand_made_totals(metric, program):
    reader = lib.load_module("metrics", metric)
    program(SESSION, TOTALS)
    assert reader.read(None) == pytest.approx(2.5 + 5.25 + 20.0)
    program(SESSION, None)  # no session began
    assert reader.read(None) is None
    program(SESSION, totals=False)
    assert reader.read(None) is None


def test_the_readers_on_the_programs_own_tracer_after_a_real_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import tracing
    from accelerate_tpu.utils.dataclasses import TracingConfig
    from chipbench import run

    previous = tracing.get_tracer().config
    tracer = tracing.configure(TracingConfig())
    try:
        jax.jit(lambda v: v * 11)(jnp.ones(5))  # set-up's compile
        run.start_trace(str(tmp_path))
        try:
            with tracing.span("serving.tick"):
                gc.collect(2)
        finally:
            jax.profiler.stop_trace()
        jax.jit(lambda v: v * 12)(jnp.ones(5))  # the check's compile, after the window
        pause = lib.load_module("metrics", "gc_pause_share.serve").read(None)
        compiled = lib.load_module("metrics", "jax_compile_s.serve").read(None)
        at_start = tracer.totals(at_session_start=True)
    finally:
        tracing.configure(previous)
    assert 0 < pause <= 100
    assert compiled == pytest.approx(sum(at_start[p]["seconds"]
                                         for p in ("jax.trace", "jax.lower", "jax.compile")))
    assert compiled > 0 and at_start["jax.compile"]["count"] >= 1
