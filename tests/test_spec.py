"""Speculative decoding suite (docs/serving.md "Speculative decoding"):

* greedy spec-vs-plain BITWISE parity — through the engine and through the
  real :class:`InferenceServer`, on the dense arena AND the paged pool
  (the ISSUE's acceptance criterion: speculation is a latency optimization,
  never a sampling change);
* seeded temperature>0 reproducibility alone-vs-packed with drafts on —
  per-slot PRNG streams survive the verify program exactly as they survive
  decode;
* the "at most THREE compiled programs" property under mixed greedy /
  sampled / drafting / non-drafting traffic (prefill_insert + decode_step
  + one verify_step signature per padded draft length);
* EOS-inside-the-window and budget-exhaustion truncation of the committed
  prefix;
* the acceptance-EWMA fallback gate (incompressible slots stop paying the
  wider verify forward, then re-probe after the cooldown);
* ``set_spec_draft_limit`` runtime clamping without recompilation (the
  serving degradation ladder's cheapest rung);
* unit contracts: ``commit_window`` drops (never clamps) overhanging
  writes on both backends, and ``verify_attention``'s query 0 reproduces
  ``paged_attention`` bitwise;
* telemetry: ``engine.stats()["spec"]`` counters and the serving
  ``spec_acceptance_rate`` / ``spec_tokens_per_step`` gauges.

Engines compile at most three programs each and are shared via a
module-scoped cache (``reset()`` restores a pristine arena between tests;
lifetime spec counters are asserted as DELTAS for that reason).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.engine import ContinuousBatchingEngine
from accelerate_tpu.inference import generate
from accelerate_tpu.kvcache import make_kv_backend
from accelerate_tpu.models.llama import LlamaConfig, create_llama
from accelerate_tpu.ops.attention import paged_attention, verify_attention
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.utils.dataclasses import ServingConfig


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    return create_llama(cfg, seed=0)


_ENGINES: dict = {}


@pytest.fixture
def get_engine(model):
    """Engine per full config tuple, cached across the module so each shape
    pays its (at most three) compiles once; reset before handout. Spec
    counters are lifetime, so tests snapshot them and assert deltas."""

    def _get(slots=4, max_len=64, prompt_bucket=16, readback_lag=0,
             kv_cache="dense", block_size=8, spec=None, spec_draft_len=4,
             attention_impl="reference"):
        key = (slots, max_len, prompt_bucket, readback_lag, kv_cache,
               block_size, spec, spec_draft_len, attention_impl)
        eng = _ENGINES.get(key)
        if eng is None:
            eng = _ENGINES[key] = ContinuousBatchingEngine(
                model, slots=slots, max_len=max_len,
                prompt_bucket=prompt_bucket, readback_lag=readback_lag,
                kv_cache=kv_cache, block_size=block_size,
                spec=spec, spec_draft_len=spec_draft_len,
                attention_impl=attention_impl,
            )
        eng.reset()
        eng.set_spec_draft_limit(eng.spec_draft_len)  # undo any test's clamp
        return eng

    return _get


def _rep_prompts(n, seed=0, unit=4, reps=3):
    """Repetitive prompts — the n-gram drafter's best case (each prompt is
    ``unit`` tokens tiled ``reps`` times, so suffix n-grams always match)."""
    rng = np.random.default_rng(seed)
    return [
        np.tile(rng.integers(1, 50, size=unit), reps).astype(np.int32).tolist()
        for _ in range(n)
    ]


def _rand_prompts(n, lens=(5, 9, 3, 12), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 255, size=lens[i % len(lens)]).tolist() for i in range(n)]


def _ref(model, prompt, budget, **kw):
    out = generate(
        model, jnp.asarray([prompt], jnp.int32), max_new_tokens=budget,
        pad_token_id=kw.pop("pad_token_id", 0), **kw,
    )
    return np.asarray(out)[0]


def _run(eng, prompts, budget, **kw):
    outs = {}
    for i, p in enumerate(prompts):
        eng.insert(p, max_new_tokens=budget, pad_token_id=0, tag=i, **kw)
    for occ in eng.drain():
        outs[occ.tag] = list(occ.tokens)
    return [outs[i] for i in range(len(prompts))]


def _spec_snapshot(eng):
    s = eng.stats()["spec"]
    return {k: s[k] for k in ("drafted", "accepted", "wasted", "verify_steps")}


def _spec_delta(eng, before):
    after = _spec_snapshot(eng)
    return {k: after[k] - before[k] for k in before}


# ------------------------------------------------------------- greedy parity
def test_greedy_spec_matches_static_reference_dense(model, get_engine):
    """Speculation must be invisible in greedy output: bitwise-identical to
    the static generate reference, while the stats prove drafts were
    actually proposed AND accepted (not a vacuous all-fallback run)."""
    eng = get_engine(spec="ngram")
    before = _spec_snapshot(eng)
    prompts = _rep_prompts(3, seed=0)
    outs = _run(eng, prompts, 20)
    for p, toks in zip(prompts, outs):
        ref = _ref(model, p, 20)
        np.testing.assert_array_equal(toks, ref[len(p):])
    d = _spec_delta(eng, before)
    assert d["verify_steps"] > 0 and d["drafted"] > 0
    assert d["accepted"] > 0  # repetitive prompts: some drafts must land
    assert d["accepted"] + d["wasted"] == d["drafted"]


def test_greedy_spec_dense_vs_paged_bitwise_identical(model, get_engine):
    """The acceptance criterion's cross-backend clause: spec output through
    the paged pool is bitwise identical to spec output through the dense
    arena (and both to the plain reference)."""
    prompts = _rep_prompts(3, seed=5)
    dense = _run(get_engine(spec="ngram"), prompts, 16)
    paged = _run(get_engine(spec="ngram", kv_cache="paged"), prompts, 16)
    assert dense == paged
    for p, toks in zip(prompts, dense):
        np.testing.assert_array_equal(toks, _ref(model, p, 16)[len(p):])


def test_greedy_spec_pallas_kernel_bitwise_identical(model, get_engine):
    """Regression: spec greedy parity must survive attention_impl="pallas" —
    the fused verify kernel replaces verify_attention inside verify_step,
    and its committed-history + in-register-window math must be invisible
    in the output. Repetitive prompts force real verify dispatches (the
    n-gram drafter never sparks on incompressible prompts, which would make
    this test vacuously pass on the decode path alone)."""
    prompts = _rep_prompts(3, seed=0)
    eng = get_engine(spec="ngram", kv_cache="paged", attention_impl="pallas")
    before = _spec_snapshot(eng)
    pallas = _run(eng, prompts, 20)
    d = _spec_delta(eng, before)
    assert d["verify_steps"] > 0 and d["drafted"] > 0  # the kernel really ran
    assert d["accepted"] > 0  # drafts landed THROUGH the fused verify kernel
    paged = _run(get_engine(spec="ngram", kv_cache="paged"), prompts, 20)
    assert pallas == paged
    for p, toks in zip(prompts, pallas):
        np.testing.assert_array_equal(toks, _ref(model, p, 20)[len(p):])


def test_spec_budget_exact_and_eos_inside_window_retires(model, get_engine):
    """A draft window may straddle the budget boundary or contain the EOS
    token: the engine must commit EXACTLY the budgeted/pre-EOS prefix —
    same contract as plain decode, verified against it."""
    eng = get_engine(spec="ngram")
    p = _rep_prompts(1, seed=7)[0]
    full = _run(eng, [p], 8)[0]
    assert len(full) == 8  # budget exact even when drafts overshoot

    eos = full[2]
    stop = full.index(eos)  # first occurrence may precede index 2
    eng.reset()
    occ = eng.insert(p, max_new_tokens=8, eos_token_id=eos, pad_token_id=0)
    eng.drain()
    assert occ.tokens == full[: stop + 1]  # up to + including EOS
    row = occ.output_row()
    assert row.shape == (len(p) + 8,)
    np.testing.assert_array_equal(row, _ref(model, p, 8, eos_token_id=eos))


def test_spec_tiny_budget_never_overcommits(model, get_engine):
    """budget=1: the drafter must stand down (the verify program itself
    samples the final token), and the single emitted token is the plain
    greedy one."""
    eng = get_engine(spec="ngram")
    p = _rep_prompts(1, seed=9)[0]
    out = _run(eng, [p], 1)[0]
    assert len(out) == 1
    np.testing.assert_array_equal(out, _ref(model, p, 1)[len(p):])


# ----------------------------------------------------- sampled reproducibility
def test_sampled_seed_reproducible_alone_vs_packed_with_spec(get_engine):
    """Rejection sampling consumes per-slot fold_in streams: a sampled
    request draws identical tokens alone (sync readback) and packed with
    strangers (deferred readback), drafts on — the engine's seeded
    contract survives speculation."""
    p = _rep_prompts(1, seed=11)[0]
    kw = dict(temperature=0.9, top_p=0.95, top_k=40, seed=123)

    alone_eng = get_engine(spec="ngram", readback_lag=0)
    alone = _run(alone_eng, [p], 10, **kw)[0]

    packed_eng = get_engine(spec="ngram", readback_lag=2)
    packed_eng.insert([7, 7, 7], max_new_tokens=12, temperature=1.3,
                      seed=999, pad_token_id=0)
    mine = packed_eng.insert(p, max_new_tokens=10, pad_token_id=0, **kw)
    packed_eng.insert([1, 2], max_new_tokens=5, temperature=0.0, pad_token_id=0)
    packed_eng.drain()
    assert alone == mine.tokens

    alone_eng.reset()
    again = _run(alone_eng, [p], 10, **kw)[0]
    assert again == alone  # same seed, same draws, every time


# --------------------------------------------------------------- program count
def test_mixed_traffic_compiles_at_most_three_programs(get_engine):
    """Greedy, sampled, drafting and non-drafting slots, every prompt
    length and budget — ONE prefill + ONE decode + ONE verify signature.
    The draft-length recompile hazard (per-step match lengths leaking into
    traced shapes) would show up here as a verify_step count > 1."""
    eng = get_engine(spec="ngram")
    rng = np.random.default_rng(13)
    rep = _rep_prompts(6, seed=13)
    for i in range(6):
        if eng.free_slots() == 0:
            eng.drain()
        # alternate drafter-friendly and incompressible prompts
        p = rep[i] if i % 2 else rng.integers(1, 255, size=int(rng.integers(1, 16))).tolist()
        eng.insert(
            p,
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
    assert stats["program_count"] <= 3
    assert all(n <= 1 for n in stats["programs"].values())
    assert stats["programs"].get("verify_step", 0) == 1  # drafts did dispatch


# ----------------------------------------------------------- fallback / clamp
def test_acceptance_ewma_gate_falls_back_then_reprobes(model, get_engine):
    """Force a slot's acceptance EWMA below the floor: the drafter must
    skip it (no verify dispatch) for the cooldown, then re-probe — and
    greedy output stays bitwise-plain throughout."""
    eng = get_engine(spec="ngram")
    before = _spec_snapshot(eng)
    p = _rep_prompts(1, seed=17)[0]
    occ = eng.insert(p, max_new_tokens=16, pad_token_id=0)
    occ.spec_ewma = 0.0  # simulate a collapsed acceptance history
    skipped_steps = 0
    while not occ.finished and skipped_steps < eng._SPEC_COOLDOWN - 1:
        eng.step()
        eng.poll()
        skipped_steps += 1
    mid = _spec_delta(eng, before)
    assert mid["verify_steps"] == 0  # gated: every step took plain decode
    eng.drain()
    after = _spec_delta(eng, before)
    assert after["verify_steps"] > 0  # cooldown elapsed -> probe draft ran
    assert occ.spec_ewma >= eng._SPEC_MIN_ACCEPT * (1 - eng._SPEC_EWMA_ALPHA)
    np.testing.assert_array_equal(occ.tokens, _ref(model, p, 16)[len(p):])


def test_set_spec_draft_limit_clamps_without_recompile(model, get_engine):
    """The serving ladder's hook: limit 0 must route every step through the
    existing decode program (no verify dispatches, parity intact); restoring
    the limit re-enables drafting — all without a fourth program."""
    eng = get_engine(spec="ngram")
    p = _rep_prompts(1, seed=19)[0]

    before = _spec_snapshot(eng)
    eng.set_spec_draft_limit(0)
    out = _run(eng, [p], 12)[0]
    assert _spec_delta(eng, before)["verify_steps"] == 0
    np.testing.assert_array_equal(out, _ref(model, p, 12)[len(p):])

    eng.set_spec_draft_limit(eng.spec_draft_len)
    before = _spec_snapshot(eng)
    out2 = _run(eng, [p], 12)[0]
    assert out2 == out
    assert _spec_delta(eng, before)["verify_steps"] > 0
    assert eng.stats()["program_count"] <= 3
    assert eng.stats()["spec"]["draft_limit"] == eng.spec_draft_len


# ------------------------------------------------------------- unit contracts
def test_commit_window_dense_drops_overhang_and_masks_count(model):
    """The scatter contract rewind depends on: only the first ``count``
    window columns land, and columns past ``max_len`` are DROPPED — a
    clamping write (dynamic_update_slice semantics) would silently corrupt
    the arena's last live column."""
    backend = make_kv_backend(
        "dense", config=model.config, slots=2, max_len=16, prompt_bucket=8,
        block_size=8, pool_blocks=None,
    )
    cache = backend.init_device_state()
    cfg = model.config
    kvh = getattr(cfg, "num_key_value_heads", None) or cfg.num_attention_heads
    rng = np.random.default_rng(0)
    win_shape = (cfg.num_hidden_layers, 2, 4, kvh, cfg.head_dim)
    window = {
        "k": jnp.asarray(rng.normal(size=win_shape), cfg.compute_dtype),
        "v": jnp.asarray(rng.normal(size=win_shape), cfg.compute_dtype),
    }
    pos = jnp.asarray([14, 3], jnp.int32)
    count = jnp.asarray([3, 2], jnp.int32)
    out = backend.commit_window(cache, window, backend.device_tables(), pos, count)
    for which in ("k", "v"):
        got = np.asarray(out[which])
        want = np.asarray(window[which])
        # slot 0: positions 14,15 take window cols 0,1; col 2 (pos 16) drops
        np.testing.assert_array_equal(got[:, 0, 14:16], want[:, 0, :2])
        assert not np.array_equal(got[:, 0, 15], want[:, 0, 2])  # no clamp
        # slot 1: count=2 -> positions 3,4 written, 5 untouched (zero)
        np.testing.assert_array_equal(got[:, 1, 3:5], want[:, 1, :2])
        np.testing.assert_array_equal(got[:, 1, 5], np.zeros_like(got[:, 1, 5]))
        np.testing.assert_array_equal(got[:, 0, :14], np.zeros_like(got[:, 0, :14]))


def test_commit_window_paged_routes_overhang_to_null_block(model):
    backend = make_kv_backend(
        "paged", config=model.config, slots=2, max_len=16, prompt_bucket=8,
        block_size=8, pool_blocks=None,
    )
    backend.acquire(0, np.arange(1, 9, dtype=np.int32), 8)
    backend.acquire(1, np.arange(10, 18, dtype=np.int32), 4)
    tables = np.asarray(backend.device_tables())
    cache = backend.init_device_state()
    cfg = model.config
    kvh = getattr(cfg, "num_key_value_heads", None) or cfg.num_attention_heads
    rng = np.random.default_rng(1)
    win_shape = (cfg.num_hidden_layers, 2, 4, kvh, cfg.head_dim)
    window = {
        "k": jnp.asarray(rng.normal(size=win_shape), cfg.compute_dtype),
        "v": jnp.asarray(rng.normal(size=win_shape), cfg.compute_dtype),
    }
    pos = jnp.asarray([14, 8], jnp.int32)
    count = jnp.asarray([3, 2], jnp.int32)
    out = backend.commit_window(
        cache, window, jnp.asarray(tables), pos, count
    )
    for which in ("k", "v"):
        got = np.asarray(out[which])
        # the pool keeps a position's heads merged on one axis
        want = np.asarray(window[which]).reshape(*win_shape[:3], -1)
        # slot 0 writes land in its SECOND block at offsets 6,7; the third
        # window column (absolute position 16 >= max_len) must hit the null
        # block, never wrap into a live one
        np.testing.assert_array_equal(got[:, tables[0, 1], 6], want[:, 0, 0])
        np.testing.assert_array_equal(got[:, tables[0, 1], 7], want[:, 0, 1])
        # slot 1 writes land in its second block at offsets 0,1; count masks
        # the remaining window columns
        np.testing.assert_array_equal(got[:, tables[1, 1], 0], want[:, 1, 0])
        np.testing.assert_array_equal(got[:, tables[1, 1], 1], want[:, 1, 1])
        np.testing.assert_array_equal(
            got[:, tables[1, 1], 2], np.zeros_like(got[:, tables[1, 1], 2])
        )
        # every allocated block other than the touched offsets stays zero
        np.testing.assert_array_equal(
            got[:, tables[0, 1], :6], np.zeros_like(got[:, tables[0, 1], :6])
        )


def test_verify_attention_query0_matches_paged_attention():
    """verify_step's first window query sits exactly where decode's single
    query sits: same mask, same math, bitwise-same output — the property
    that makes draft_len=0 verify rows reproduce decode_step."""
    rng = np.random.default_rng(2)
    b, w, h, h_kv, d = 2, 3, 4, 2, 8
    blocks, bs, bpr = 5, 4, 2
    q = jnp.asarray(rng.normal(size=(b, w, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(blocks, bs, h_kv, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(blocks, bs, h_kv, d)), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([5, 2], jnp.int32)
    ver = verify_attention(q, k_pool, v_pool, tables, pos)
    dec = paged_attention(q[:, :1], k_pool, v_pool, tables, pos)
    assert ver.shape == (b, w, h, d)
    np.testing.assert_array_equal(np.asarray(ver[:, :1]), np.asarray(dec))


def _step_family(name, compute_dtype):
    from accelerate_tpu.models.gpt2 import GPT2Config, create_gpt2

    if name == "gpt2":
        return create_gpt2(GPT2Config.tiny(compute_dtype=compute_dtype), seed=0)
    features = {
        "llama_gqa_bias_softcap": dict(attention_bias=True, attn_logit_softcap=30.0),
        # the window downgrades the kernel to the reference attend
        "llama_alternating_window": dict(
            sliding_window=6, alternating_sliding_window=True, attn_logit_softcap=30.0),
    }[name]
    return create_llama(LlamaConfig.tiny(compute_dtype=compute_dtype, **features), seed=1)


@pytest.mark.parametrize("store", ["arena", "pool_reference", "pool_pallas"])
@pytest.mark.parametrize(
    "family", ["gpt2", "llama_gqa_bias_softcap", "llama_alternating_window"])
def test_verify_step_at_a_window_of_one_is_the_decode_step(family, store):
    """A family has one step block, and decode is that block at a window of
    one: ``verify_step`` over one token gives ``decode_step``'s logits
    bitwise, in bfloat16 compute as served, wherever the one attention
    (``ops.attention.cache_attention``) runs: the arena and the gathered pool.
    The decode and the verify kernel are two schedules of the same sums (a
    walk over chunks of the pool with the column committed; history from the
    pool and the window in registers): they agree to float32 rounding and in
    every argmax, at the parent as here. The window's keys and values are
    what the decode step wrote at ``pos``."""
    from accelerate_tpu.kvcache import PagedKVLayout, pool_from_dense

    model = _step_family(family, jnp.float32 if store == "pool_pallas" else jnp.bfloat16)
    config, params = model.config, model.params
    fam = config.serving_family()
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(1, 255, size=(2, 16)), jnp.int32)
    last = jnp.asarray([8, 12], jnp.int32)
    _, cache = fam.prefill_at(config, params, ids, 32, last)
    layout = None
    if store != "arena":
        cache, tables = pool_from_dense(cache, 8, quantized=False)
        layout = PagedKVLayout(tables, 8, config.compute_dtype, fam.head_dim,
                               attention_impl=store.split("_")[1])
    token = jnp.asarray([[7], [200]], jnp.int32)
    pos = last + 1
    window_logits, window = fam.verify_step(config, params, cache, token, pos, kv_layout=layout)
    logits, new_cache = fam.decode_step(config, params, cache, token, pos, kv_layout=layout)
    assert window_logits.shape == (2, 1, config.vocab_size)
    if store == "pool_pallas":
        np.testing.assert_allclose(np.asarray(window_logits[:, 0]), np.asarray(logits), atol=1e-5)
        np.testing.assert_array_equal(np.argmax(window_logits[:, 0], -1), np.argmax(logits, -1))
    else:
        np.testing.assert_array_equal(np.asarray(window_logits[:, 0]), np.asarray(logits))
    if store == "arena":
        for which in ("k", "v"):
            wrote = np.stack([np.asarray(new_cache[which][:, r, int(pos[r])]) for r in range(2)], 1)
            np.testing.assert_array_equal(np.asarray(window[which][:, :, 0]), wrote)


# ------------------------------------------------------------ server plumbing
@pytest.mark.parametrize("kv_cache", ["dense", "paged"])
def test_server_spec_greedy_parity_and_gauges(model, get_engine, kv_cache):
    """End-to-end through the real InferenceServer: greedy parity with more
    requests than slots (slot-reuse admission), plus the spec gauges the
    worker publishes every tick."""
    eng = get_engine(slots=2, readback_lag=2, spec="ngram", kv_cache=kv_cache)
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2,
        kv_cache=kv_cache, speculative="ngram", spec_draft_len=4,
    )
    # short tiled units draft early enough that the acceptance-EWMA gate
    # (which decays on matchless steps) never parks these slots
    prompts = _rep_prompts(4, seed=31, unit=2, reps=6)
    budgets = [12, 8, 10, 6]
    with InferenceServer(model, cfg, engine=eng) as srv:
        futs = [
            srv.submit(p, max_new_tokens=b, pad_token_id=0)
            for p, b in zip(prompts, budgets)
        ]
        res = [f.result(timeout=120) for f in futs]
        snap = srv.metrics.snapshot()
    for p, b, r in zip(prompts, budgets, res):
        np.testing.assert_array_equal(r.tokens, _ref(model, p, b))
    spec = eng.stats()["spec"]
    assert spec["drafted"] > 0
    assert snap["serving/spec_acceptance_rate"] == pytest.approx(
        spec["acceptance_rate"]
    )
    assert snap["serving/spec_tokens_per_step"] == pytest.approx(
        spec["tokens_per_step"]
    )
    assert spec["tokens_per_step"] >= 1.0  # a verify step never emits < 1


def test_spec_stats_shape(get_engine):
    s = get_engine(spec="ngram").stats()["spec"]
    assert s["mode"] == "ngram" and s["draft_len"] == 4
    for k in ("drafted", "accepted", "wasted", "verify_steps",
              "acceptance_rate", "acceptance_ewma", "tokens_per_step",
              "draft_limit"):
        assert k in s
    off = get_engine().stats()["spec"]
    assert off["mode"] == "off" and off["draft_len"] == 0


def test_serving_config_validates_spec_knobs():
    with pytest.raises(ValueError, match="speculative"):
        ServingConfig(speculative="eagle", mode="continuous")
    with pytest.raises(ValueError, match="continuous"):
        ServingConfig(speculative="ngram", mode="static")
    with pytest.raises(ValueError, match="spec_draft_len"):
        ServingConfig(speculative="ngram", mode="continuous", spec_draft_len=0)
    ServingConfig(speculative="ngram", mode="continuous")  # valid
    ServingConfig(spec_draft_len=0)  # inert when speculation is off


def test_engine_validates_spec_knobs(model):
    with pytest.raises(ValueError, match="spec must be"):
        ContinuousBatchingEngine(model, slots=1, max_len=8, spec="medusa")
    with pytest.raises(ValueError, match="spec_draft_len"):
        ContinuousBatchingEngine(model, slots=1, max_len=8, spec="ngram",
                                 spec_draft_len=0)
