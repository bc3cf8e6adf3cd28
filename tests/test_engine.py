"""Continuous-batching engine suite (docs/serving.md):

* slot lifecycle — admit → decode → retire → reuse, with KV isolation
  between successive occupants of the same slot;
* per-slot seed reproducibility — a sampled request yields identical
  tokens whether it runs alone or packed with strangers;
* per-slot budget + EOS retirement semantics;
* greedy static-vs-continuous output parity (including slot reuse) through
  the real :class:`InferenceServer`;
* drain-under-load with zero dropped futures;
* the "exactly two compiled programs" property under mixed traffic;
* the static-mode satellites: ``wasted_decode_steps`` telemetry and the
  attach-time ``ACCELERATE_GENERATE_CACHE_MAX`` read.

Engines compile two programs each, so tests share per-shape engines via a
module-scoped cache (``reset()`` restores a pristine arena between tests).
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from accelerate_tpu import tracing
from accelerate_tpu.engine import ContinuousBatchingEngine
from accelerate_tpu.inference import (
    generate,
    generate_cache_stats,
    last_generate_stats,
)
from accelerate_tpu.models.llama import LlamaConfig, create_llama
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.utils.dataclasses import ServingConfig
from accelerate_tpu.utils.fault import (
    BatchExecutionError,
    FaultInjected,
    ServerDrainingError,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    return create_llama(cfg, seed=0)


_ENGINES: dict = {}


@pytest.fixture
def get_engine(model):
    """Engine per (slots, max_len, prompt_bucket, lag), cached across the
    module so each shape pays its two compiles once; reset before handout."""

    def _get(slots=4, max_len=64, prompt_bucket=16, readback_lag=2):
        key = (slots, max_len, prompt_bucket, readback_lag)
        eng = _ENGINES.get(key)
        if eng is None:
            eng = _ENGINES[key] = ContinuousBatchingEngine(
                model,
                slots=slots,
                max_len=max_len,
                prompt_bucket=prompt_bucket,
                readback_lag=readback_lag,
            )
        eng.reset()
        return eng

    return _get


def _prompts(n, lens=(5, 9, 3, 12, 7, 4, 10, 6), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 255, size=lens[i % len(lens)]).tolist() for i in range(n)]


def _ref(model, prompt, budget, **kw):
    out = generate(
        model, jnp.asarray([prompt], jnp.int32), max_new_tokens=budget,
        pad_token_id=kw.pop("pad_token_id", 0), **kw,
    )
    return np.asarray(out)[0]


# --------------------------------------------------------------- slot lifecycle
def test_slot_lifecycle_reuse_keeps_kv_isolation(model, get_engine):
    """Three admission waves through the same 2-slot arena: every wave's
    tokens must match a solo static generate — a reused slot leaking its
    previous occupant's KV would corrupt wave 2+ but not wave 1."""
    eng = get_engine(slots=2)
    waves = [_prompts(2, seed=s) for s in (1, 2, 3)]
    budgets = [5, 7]
    for wave in waves:
        occs = [
            eng.insert(p, max_new_tokens=b, pad_token_id=0, tag=i)
            for i, (p, b) in enumerate(zip(wave, budgets))
        ]
        retired = eng.drain()
        assert sorted(o.tag for o in retired) == [0, 1]
        for p, b, occ in zip(wave, budgets, occs):
            np.testing.assert_array_equal(occ.output_row(), _ref(model, p, b))
    stats = eng.stats()
    assert stats["free"] == 2 and stats["live"] == 0


def test_insert_requires_free_slot_and_valid_shape(get_engine):
    eng = get_engine(slots=2)
    eng.insert([1, 2, 3], max_new_tokens=4, tag="a")
    eng.insert([4, 5], max_new_tokens=4, tag="b")
    with pytest.raises(RuntimeError, match="free arena slot"):
        eng.insert([6], max_new_tokens=2, tag="c")
    with pytest.raises(ValueError, match="prompt bucket"):
        eng.validate_request(17, 4)
    with pytest.raises(ValueError, match="KV arena length"):
        eng.validate_request(10, 60)
    eng.drain()


# ------------------------------------------------------- seed reproducibility
def test_per_slot_seed_reproducible_alone_vs_packed(model, get_engine):
    """A sampled request's draws come from ITS per-slot PRNG key: the same
    request produces identical tokens alone (sync readback) and packed with
    strangers at other seeds/temperatures (deferred readback) — the
    property static mode could only buy by seed-keying batches."""
    p = [5, 9, 17, 3]
    kw = dict(
        max_new_tokens=8, temperature=0.9, top_p=0.95, top_k=40, seed=123,
        pad_token_id=0,
    )
    eng0 = get_engine(readback_lag=0)
    alone = eng0.insert(p, **kw)
    eng0.drain()

    eng = get_engine(readback_lag=2)
    eng.insert([7, 7, 7], max_new_tokens=10, temperature=1.3, seed=999, pad_token_id=0)
    packed = eng.insert(p, **kw)
    eng.insert([1, 2], max_new_tokens=5, temperature=0.0, pad_token_id=0)
    eng.drain()
    assert alone.tokens == packed.tokens

    eng0.reset()
    again = eng0.insert(p, **kw)
    eng0.drain()
    assert again.tokens == alone.tokens  # same seed, same draws, every time


# --------------------------------------------------------- budget + EOS retire
def test_budget_honored_exactly_and_eos_retires_early(model, get_engine):
    eng = get_engine(readback_lag=0)
    p = _prompts(1, seed=7)[0]
    full = eng.insert(p, max_new_tokens=6, pad_token_id=0, tag="full")
    eng.drain()
    assert len(full.tokens) == 6  # budget exact, no EOS configured

    # use an actually-emitted token as EOS: retire at its FIRST occurrence
    eos = full.tokens[2]
    stop = full.tokens.index(eos)  # may appear before index 2
    eng.reset()
    early = eng.insert(p, max_new_tokens=6, eos_token_id=eos, pad_token_id=0)
    eng.drain()
    assert early.tokens == full.tokens[: stop + 1]  # up to + including EOS
    # output_row pads the unused budget so shapes match static generate
    row = early.output_row()
    assert row.shape == (len(p) + 6,)
    np.testing.assert_array_equal(row, _ref(model, p, 6, eos_token_id=eos))


def test_cancel_frees_slot_and_ignores_stale_ring_tokens(model, get_engine):
    eng = get_engine(slots=2, readback_lag=2)
    victim = eng.insert([1, 2, 3], max_new_tokens=20, pad_token_id=0)
    eng.step()
    eng.cancel(victim)
    assert eng.free_slots() == 2 and victim.finished
    before = len(victim.tokens)
    # a fresh occupant can take the slot immediately; stale ring entries for
    # the cancelled occupant must not append to it or corrupt the newcomer
    p = _prompts(1, seed=9)[0]
    fresh = eng.insert(p, max_new_tokens=5, pad_token_id=0)
    eng.drain()
    assert len(victim.tokens) == before
    np.testing.assert_array_equal(fresh.output_row(), _ref(model, p, 5))


# ------------------------------------------------------------- program count
def test_mixed_traffic_compiles_exactly_two_programs(get_engine):
    """Greedy, sampled (several seeds/temps/top_k/top_p), every prompt
    length and budget — ONE prefill signature + ONE decode signature. This
    is the acceptance-criteria stat the bench gate also asserts."""
    eng = get_engine()
    rng = np.random.default_rng(11)
    for i in range(6):
        if eng.free_slots() == 0:
            eng.drain()
        plen = int(rng.integers(1, 16))
        eng.insert(
            rng.integers(1, 255, size=plen).tolist(),
            max_new_tokens=int(rng.integers(1, 12)),
            temperature=float(i % 3) * 0.5,
            top_k=int(rng.integers(0, 50)) or None,
            top_p=0.9 if i % 2 else None,
            seed=i * 17,
            pad_token_id=0,
        )
        if i % 2:
            eng.step()
            eng.poll()
    eng.drain()
    stats = eng.stats()
    assert stats["programs"] == {"prefill_insert": 1, "decode_step": 1}
    assert stats["program_count"] <= 2


# ---------------------------------------------------- remote-prefill parity
@pytest.mark.parametrize("rung,bucket,max_len,lens", [
    (8, 8, 32, (5, 7)),  # a bucket of 8: one rung
    (128, 256, 512, (100, 128)),  # the half rung of a bucket of 256
    (256, 256, 512, (129, 250)),  # the whole bucket
], ids=["one_rung", "half_rung", "full_rung"])
def test_remote_prefill_bitwise_parity(model, get_engine, rung, bucket, max_len, lens):
    """prefill_remote + insert_prefilled must be bitwise identical to a
    plain insert — same forward, same sample, same key evolution — for
    greedy AND sampled requests, including a downward budget override at
    commit time (the disaggregated path docs/serving.md promises), on
    each rung a prompt can take."""
    eng = get_engine(slots=2, max_len=max_len, prompt_bucket=bucket)
    prompts = _prompts(2, lens=lens, seed=11)
    assert [eng._rung(len(p)) for p in prompts] == [rung, rung]
    cases = [
        dict(temperature=0.0),
        dict(temperature=0.8, top_k=40, top_p=0.9, seed=13),
    ]
    plain = []
    for p, kw in zip(prompts, cases):
        occ = eng.insert(p, max_new_tokens=6, pad_token_id=0, **kw)
        eng.drain()
        plain.append(occ.output_row())
        eng.reset()
    trace_id = f"remote-rung-{bucket}-{rung}"
    for p, kw, want in zip(prompts, cases, plain):
        pre = eng.prefill_remote(p, max_new_tokens=6, pad_token_id=0,
                                 trace_id=trace_id, **kw)
        assert eng.accepts_prefill(pre)
        occ = eng.insert_prefilled(pre, tag="rp")
        eng.drain()
        np.testing.assert_array_equal(occ.output_row(), want)
        eng.reset()
    spans = tracing.get_tracer().spans(trace_id=trace_id, name="engine.prefill")
    assert [s.attrs["bucket"] for s in spans] == [rung, rung]
    # Budget can only be clamped downward at commit; the clamped result
    # matches a plain insert at the clamped budget, padding and all.
    p, kw = prompts[0], cases[0]
    occ = eng.insert(p, max_new_tokens=3, pad_token_id=0, **kw)
    eng.drain()
    want3 = occ.output_row()
    eng.reset()
    pre = eng.prefill_remote(p, max_new_tokens=6, pad_token_id=0, **kw)
    with pytest.raises(ValueError):
        eng.insert_prefilled(pre, max_new_tokens=7)
    occ = eng.insert_prefilled(pre, max_new_tokens=3)
    eng.drain()
    np.testing.assert_array_equal(occ.output_row(), want3)


# ------------------------------------------------- static vs continuous parity
def test_greedy_static_vs_continuous_parity_through_server(model, get_engine):
    """Same greedy requests, both scheduling modes, identical tokens — with
    more requests than slots so parity also covers slot-reuse admission."""
    eng = get_engine(slots=2)
    prompts = _prompts(6, seed=21)
    budgets = [6, 4, 8, 5, 7, 3]
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2,
    )
    with InferenceServer(model, cfg, engine=eng) as srv:
        futs = [
            srv.submit(p, max_new_tokens=b, pad_token_id=0)
            for p, b in zip(prompts, budgets)
        ]
        cont = [f.result(timeout=120) for f in futs]
    for p, b, res in zip(prompts, budgets, cont):
        np.testing.assert_array_equal(res.tokens, _ref(model, p, b))
        assert res.ttft_s is not None and res.ttft_s <= res.latency_s + 1e-9
    assert srv.metrics["completed"] == 6
    assert srv.metrics["engine_inserts"] == 6
    assert srv.metrics["engine_retired"] == 6


# --------------------------------------------------------------- drain / faults
def test_drain_under_load_drops_no_future(model, get_engine):
    """Drain mid-flight: every submitted future resolves — in-slot requests
    finish with real tokens, queued ones get the retriable draining error,
    nothing hangs."""
    eng = get_engine(slots=2)
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2, max_queue=64,
    )
    prompts = _prompts(10, seed=31)
    srv = InferenceServer(model, cfg, engine=eng)
    try:
        futs = [srv.submit(p, max_new_tokens=24, pad_token_id=0) for p in prompts]
        # let the scheduler pick up some work, then pull the plug
        deadline = time.monotonic() + 30
        while srv.metrics["engine_inserts"] == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.drain(timeout=120)
    finally:
        srv.close()
    outcomes = {"ok": 0, "draining": 0}
    for p, f in zip(prompts, futs):
        assert f.done(), "drain left a future unresolved"
        try:
            res = f.result(timeout=0)
            np.testing.assert_array_equal(res.tokens, _ref(model, p, 24))
            outcomes["ok"] += 1
        except ServerDrainingError:
            outcomes["draining"] += 1
    assert outcomes["ok"] + outcomes["draining"] == 10
    assert outcomes["ok"] >= 1  # in-flight slots finished, not dropped


def test_engine_failure_fails_inflight_and_server_recovers(model, get_engine, fault_inject):
    eng = get_engine(slots=2)
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2,
    )
    with InferenceServer(model, cfg, engine=eng) as srv:
        fault_inject("serving_before_batch:raise")
        fut = srv.submit([1, 2, 3], max_new_tokens=4, pad_token_id=0)
        with pytest.raises(BatchExecutionError) as ei:
            fut.result(timeout=60)
        assert isinstance(ei.value.__cause__, FaultInjected)
        fault_inject("")  # disarm: the next request must serve normally
        p = _prompts(1, seed=41)[0]
        ok = srv.submit(p, max_new_tokens=5, pad_token_id=0).result(timeout=120)
        np.testing.assert_array_equal(ok.tokens, _ref(model, p, 5))
    assert srv.metrics["batch_failures"] >= 1


def test_reset_lets_go_of_the_old_state_before_it_makes_the_new(get_engine, monkeypatch):
    """A server's worker resets its engine whenever it stops; the old pool is
    dropped before the fresh one is allocated, so a pool that fills most of the
    chip is never held twice (the LongCat cell's 4.03e9 B pool beside 10.35e9 B of
    weights could not be: PERF.md, PR 38)."""
    eng = get_engine(slots=2)
    made = eng._init_state
    seen = []

    def init_state():
        seen.append((eng._donated, eng._carried))
        return made()

    monkeypatch.setattr(eng, "_init_state", init_state)
    eng.reset()
    assert seen == [(None, None)]
    assert eng._donated is not None and eng._carried is not None


def test_submissions_from_many_threads_all_resolve(model, get_engine):
    eng = get_engine(slots=4)
    cfg = ServingConfig(
        mode="continuous", engine_slots=4, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2,
    )
    prompts = _prompts(12, seed=51)
    results: dict = {}
    with InferenceServer(model, cfg, engine=eng) as srv:

        def client(i):
            res = srv.submit(prompts[i], max_new_tokens=4, pad_token_id=0).result(120)
            results[i] = res.tokens

        threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert len(results) == 12
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(results[i], _ref(model, p, 4))


# ------------------------------------------------------- static-mode satellites
def test_static_wasted_decode_steps_counter(model):
    p = _prompts(1, seed=61)[0]
    out = _ref(model, p, 8)
    assert last_generate_stats(model)["wasted_decode_steps"] == 0  # no EOS set
    eos = int(out[len(p) + 1])  # second emitted token → ~6 frozen steps
    _ref(model, p, 8, eos_token_id=eos)
    wasted = last_generate_stats(model)["wasted_decode_steps"]
    assert wasted == 6  # 8-step scan, done after step 2, one row


def test_generate_cache_max_read_at_attach_time(monkeypatch):
    monkeypatch.setenv("ACCELERATE_GENERATE_CACHE_MAX", "1")
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    fresh = create_llama(cfg, seed=1)  # cache attaches on first generate
    generate(fresh, jnp.asarray([[1, 2, 3]], jnp.int32), max_new_tokens=2)
    generate(fresh, jnp.asarray([[1, 2, 3, 4]], jnp.int32), max_new_tokens=2)
    stats = generate_cache_stats(fresh)
    assert stats["max"] == 1
    assert stats["size"] == 1  # second structural key evicted the first
