"""LFM2 (gated short convolutions beside attention, dropless sigmoid-routed
experts) against its plain reference, at the tiny preset on the CPU.

The reference is the benchmark's own file, ``chipbench/reference/lfm2.py``,
loaded by its path: one description of the model, kept where the chip run's
``correct`` reads it. It imports nothing of the benchmark or of the program.

Tolerances. In float32 the program and the reference do the same sums in another
order: logits agree to ``F32_TOL`` (2e-5; read: 1e-6), and the same test run with
bfloat16 compute reads a hundred times that, which each test that states the
tolerance checks, so that a path quietly computing lower would fail it.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import tracing
from accelerate_tpu.engine import ContinuousBatchingEngine, RemotePrefill
from accelerate_tpu.inference import generate
from accelerate_tpu.kvcache import PagedKVLayout, pool_from_dense
from accelerate_tpu.models.lfm2 import (
    ATTENTION,
    CONV,
    Lfm2Config,
    create_lfm2,
    lfm2_apply,
    lfm2_decode_step,
    lfm2_loss,
    lfm2_prefill_at,
)
from accelerate_tpu.ops.moe import dropless_moe
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.utils.dataclasses import ServingConfig, TracingConfig

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "chipbench", "reference", "lfm2.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load_reference()
PUBLISHED_TYPES = Lfm2Config.lfm2_8b_a1b().layer_types


def as_file(config: Lfm2Config) -> dict:
    """The configuration as the benchmark's file states it: what the reference reads."""
    cfg = {f: getattr(config, f) for f in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "num_dense_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts", "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
        "routed_scaling_factor", "conv_L_cache", "norm_eps", "rope_theta", "vocab_size")}
    cfg["layer_types"] = list(config.layer_types)
    return cfg


def flat(tree, prefix="") -> dict:
    out = {}
    for key, value in tree.items():
        name = f"{prefix}.{key}" if prefix else key
        out.update(flat(value, name) if isinstance(value, dict) else {name: value})
    return out


def tiny(dtype=jnp.float32, **overrides):
    config = Lfm2Config.tiny(param_dtype=jnp.float32, compute_dtype=dtype, **overrides)
    return config, create_lfm2(config, seed=3)


def reference_logits(config, params, ids):
    one = jax.jit(lambda weights, row: reference.logits(as_file(config), weights, row))
    return np.stack([np.asarray(one(flat(params), row)) for row in ids])


def forward(config, params, ids):
    return np.asarray(jax.jit(lambda p, i: lfm2_apply(config, p, i))(params, jnp.asarray(ids)))


def tokens(shape, seed=0, vocab=256):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0, vocab), np.int32)


# --------------------------------------------------------------- the full forward
def test_the_tree_and_the_spec_name_the_same_leaves():
    config, model = tiny()
    spec = {name: tuple(shape) for name, shape, *_ in reference.weight_spec(as_file(config))}
    assert {name: tuple(leaf.shape) for name, leaf in flat(model.params).items()} == spec
    assert create_lfm2(config, abstract=True).num_parameters == model.num_parameters
    # the published model, shapes only: 8.3B parameters, 18 convolutions and 6 attention layers
    full = Lfm2Config.lfm2_8b_a1b()
    assert (full.conv_layers, full.attention_layers, full.num_moe_layers) == (18, 6, 22)
    assert create_lfm2(full, abstract=True).num_parameters == 8_339_930_560


@pytest.mark.parametrize("layers", ["tiny", "published_24"])
def test_full_forward_matches_the_reference(layers):
    """Any ``layer_types`` list runs: the tiny preset's, and the published 24
    entries, whose tail (attention at 18 and 21) is not periodic."""
    overrides = {} if layers == "tiny" else dict(
        num_hidden_layers=24, layer_types=PUBLISHED_TYPES, num_dense_layers=2)
    config, model = tiny(**overrides)
    ids = tokens((2, 20), seed=1)
    want = reference_logits(config, model.params, ids)
    got = forward(config, model.params, ids)
    assert got.dtype == np.float32 and got.shape == (2, 20, config.vocab_size)
    assert np.abs(got - want).max() < F32_TOL
    if layers == "tiny":  # the tolerance tells bfloat16 compute from float32
        low = Lfm2Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)
        assert np.abs(forward(low, model.params, ids) - want).max() > 20 * F32_TOL


def test_loss_equals_the_references_and_has_a_gradient():
    config, model = tiny()
    ids = tokens((2, 12), seed=2)
    logits = reference_logits(config, model.params, ids)[:, :-1]
    logp = logits - np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)) \
        - logits.max(-1, keepdims=True)
    want = -np.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    def loss(params):
        return lfm2_loss(model.bind(params), {"input_ids": jnp.asarray(ids)})

    value, grads = jax.jit(jax.value_and_grad(loss))(model.params)
    assert abs(float(value) - want) < F32_TOL
    moved = {name: float(jnp.abs(g).max()) for name, g in flat(grads).items()}
    # the expert bias takes part in the choice only: no gradient reaches it
    assert moved.pop("moe.expert_bias") == 0.0
    assert all(v > 0 for v in moved.values()), [n for n, v in moved.items() if v == 0]


# ------------------------------------------------- prefill, then decode, through a cache
def _decode_through(config, params, ids, lengths, bucket, steps, cache_kind):
    """Prefill right-padded prompts of ``lengths`` in a ``bucket``, then feed the
    sequences' own next tokens for ``steps`` steps. Returns the logits at every
    position from each prompt's last on, ``(rows, 1 + steps, vocab)``."""
    rows = len(lengths)
    max_len = 32
    padded = np.zeros((rows, bucket), np.int32)
    for r, n in enumerate(lengths):
        padded[r, :n] = ids[r, :n]
    last = jnp.asarray(lengths, jnp.int32) - 1
    logits, cache, counters = jax.jit(
        lambda p, ids, last: lfm2_prefill_at(config, p, ids, max_len, last)
    )(params, jnp.asarray(padded), last)
    assert counters["moe_rows"].shape == (config.num_moe_layers, config.num_experts)
    assert int(counters["moe_rows"].sum()) == (
        rows * bucket * config.num_experts_per_tok * config.num_moe_layers)
    layout = None
    if cache_kind != "dense":
        cache, tables = pool_from_dense(cache, 8, quantized=False)
        assert cache["k"].shape[0] == config.attention_layers  # not the model's depth
        layout = PagedKVLayout(tables, 8, config.compute_dtype, config.head_dim,
                               attention_impl=cache_kind)
    out = [logits]
    pos = jnp.asarray(lengths, jnp.int32)
    step = jax.jit(lambda cache, token, pos: lfm2_decode_step(
        config, params, cache, token, pos, kv_layout=layout))
    for t in range(steps):
        token = jnp.asarray([[ids[r, lengths[r] + t]] for r in range(rows)], jnp.int32)
        logits, cache, _ = step(cache, token, pos + t)
        out.append(logits)
    return np.stack([np.asarray(x) for x in out], axis=1)


@pytest.mark.parametrize("cache_kind", ["dense", "reference", "pallas"])
def test_prefill_then_decode_matches_the_full_forward(cache_kind):
    """Prompts shorter than their bucket (5 and 11 of 16), so the convolution's
    state has to be the one at the true last position; then six tokens through
    both kinds of state: the dense arena, the paged pool gathered and committed,
    the paged pool under the Pallas kernel."""
    config, model = tiny()
    ids = tokens((2, 24), seed=4)
    lengths, steps = [5, 11], 6
    want = reference_logits(config, model.params, ids)
    got = _decode_through(config, model.params, ids, lengths, 16, steps, cache_kind)
    for r, n in enumerate(lengths):
        assert np.abs(got[r] - want[r, n - 1 : n + steps]).max() < F32_TOL, (cache_kind, r)
    if cache_kind == "pallas":  # the same through bfloat16 compute fails the tolerance
        low = Lfm2Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)
        coarse = _decode_through(low, model.params, ids, lengths, 16, steps, cache_kind)
        assert np.abs(coarse[0] - want[0, 4 : 5 + steps]).max() > 20 * F32_TOL


def test_the_state_at_the_padded_end_would_be_wrong():
    """What ``last_index`` is for: the recurrent state taken at the bucket's end
    differs from the one at the prompt's last position."""
    config, model = tiny()
    padded = np.zeros((1, 16), np.int32)
    padded[0, :5] = tokens((5,), seed=5)
    prefill = jax.jit(lambda p, ids, last: lfm2_prefill_at(config, p, ids, 32, last)[1]["recurrent"])
    at = lambda last: prefill(model.params, jnp.asarray(padded), jnp.asarray([last]))  # noqa: E731
    true, end = at(4), at(15)
    assert true.shape == (config.conv_layers, 1, config.conv_L_cache - 1, config.hidden_size)
    assert float(jnp.abs(true - end).max()) > 1e-3


# ------------------------------------------------------------------- the engine
def engine_for(model, **kw):
    kw = {**dict(slots=4, max_len=48, prompt_bucket=16, kv_cache="paged", block_size=4,
                 attention_impl="pallas", readback_lag=2), **kw}
    return ContinuousBatchingEngine(model, **kw)


@pytest.fixture(scope="module")
def served():
    """The tiny model in bfloat16 compute, as it is served."""
    config = Lfm2Config.tiny()
    return create_lfm2(config, seed=3)


@pytest.mark.parametrize("kv_cache,impl", [("dense", "reference"), ("paged", "reference"),
                                           ("paged", "pallas")])
def test_engine_tokens_equal_generate(kv_cache, impl):
    config, model = tiny()
    prompts = tokens((2, 9), seed=6)
    want = np.asarray(generate(model, prompts, max_new_tokens=7))
    eng = engine_for(model, kv_cache=kv_cache, attention_impl=impl)
    occs = [eng.insert(p, max_new_tokens=7, pad_token_id=0) for p in prompts]
    eng.drain()
    for occ, row in zip(occs, want):
        assert occ.output_row().tolist() == row.tolist()
    assert eng.stats()["programs"] == {"prefill_insert": 1, "decode_step": 1}


def test_per_slot_seed_reproducible_alone_vs_packed(served):
    """``tests/test_engine.py``'s property for LFM2: without drops and without a
    capacity a row's experts, and so its tokens, do not depend on who else is
    in the batch. Sampled, in bfloat16 compute, packed with strangers."""
    p = [5, 9, 17, 3, 200, 41]
    kw = dict(max_new_tokens=12, temperature=0.9, top_p=0.95, top_k=40, seed=123, pad_token_id=0)
    alone_engine = engine_for(served, readback_lag=0)
    alone = alone_engine.insert(p, **kw)
    alone_engine.drain()

    eng = engine_for(served)
    eng.insert([7, 7, 7], max_new_tokens=14, temperature=1.3, seed=999, pad_token_id=0)
    packed = eng.insert(p, **kw)
    eng.insert([1, 2], max_new_tokens=5, temperature=0.0, pad_token_id=0)
    eng.insert(list(range(30, 45)), max_new_tokens=9, temperature=0.7, seed=5, pad_token_id=0)
    eng.drain()
    assert alone.tokens == packed.tokens


def test_a_prompt_sent_twice_gives_the_tokens_it_gives_once(served):
    """A prefix-cache hit shares blocks of keys and values; the sharer's prefill
    still runs over its whole prompt, so its convolution state is its own. Sent
    again while the first still decodes, and again after it has retired (the
    blocks then come from the cached tier), into a slot another request left."""
    prompt = tokens((11,), seed=7).tolist()  # two whole blocks of 4 to share
    kw = dict(max_new_tokens=8, pad_token_id=0)
    eng = engine_for(served)
    once = eng.insert(prompt, **kw)
    eng.step()
    twice = eng.insert(prompt, **kw)  # the first is still live
    eng.drain()
    assert eng.stats()["kv"]["prefix_hits"] == 2
    other = eng.insert([9, 8, 7, 6, 5, 4, 3], max_new_tokens=5, pad_token_id=0)
    eng.drain()
    third = eng.insert(prompt, **kw)  # after both retired: a released slot, cached blocks
    eng.drain()
    assert eng.stats()["kv"]["prefix_hits"] == 4
    assert once.tokens == twice.tokens == third.tokens and len(once.tokens) == 8
    assert len(other.tokens) == 5


def test_remote_prefill_carries_the_recurrent_state():
    """``prefill_remote`` -> ``insert_prefilled`` hands the convolution state over
    with the keys and values, by reference and over the wire encoding."""
    config, model = tiny()
    prompt = tokens((7,), seed=8).tolist()
    kw = dict(max_new_tokens=6, pad_token_id=0)
    eng = engine_for(model)
    plain = eng.insert(prompt, **kw)
    eng.drain()
    pre = eng.prefill_remote(prompt, **kw)
    assert pre.cache["recurrent"].shape[:2] == (config.conv_layers, 1)
    handed = eng.insert_prefilled(pre)
    eng.drain()
    wired = eng.insert_prefilled(RemotePrefill.from_bytes(pre.to_bytes(), engine=eng))
    eng.drain()
    assert plain.tokens == handed.tokens == wired.tokens


def test_what_is_not_carried_is_refused_by_name(served):
    with pytest.raises(ValueError, match=r"speculative decoding needs a verify_step.*recurrent-state snapshot"):
        engine_for(served, spec="ngram")
    with pytest.raises(ValueError, match=r"chunked prefill needs a verify_step"):
        engine_for(served, prefill_chunk=8)
    with pytest.raises(ValueError, match=r"kv_host_tier_bytes cannot serve a family with recurrent"):
        engine_for(served, host_tier_bytes=1 << 20)
    with pytest.raises(ValueError, match="verify_step"):  # the server hands the options through
        InferenceServer(served, ServingConfig(
            mode="continuous", kv_cache="paged", engine_slots=2, engine_max_len=32,
            engine_prompt_bucket=8, speculative="ngram"))


def test_the_server_serves_it_on_the_normal_path():
    config, model = tiny()
    prompts = tokens((3, 6), seed=9)
    want = np.asarray(generate(model, prompts, max_new_tokens=5))
    cfg = ServingConfig(mode="continuous", kv_cache="paged", attention_impl="pallas",
                        engine_slots=2, engine_max_len=32, engine_prompt_bucket=8,
                        engine_block_size=4)
    with InferenceServer(model, cfg) as server:
        futures = [server.submit(p, max_new_tokens=5, pad_token_id=0) for p in prompts]
        got = [np.asarray(f.result(timeout=300).tokens) for f in futures]
        stats = server.engine.stats()
    for row, tokens_ in zip(want, got):
        assert tokens_.tolist() == row.tolist()
    assert stats["programs"] == {"prefill_insert": 1, "decode_step": 1}
    # 3 convolutions x 2 slots x 2 rows of 64, float32 here
    assert stats["recurrent_state_bytes"] == 3 * 2 * 2 * 64 * 4 == stats["kv"]["recurrent_state_bytes"]


def test_decode_program_carries_the_pool_whole(served):
    """PR 28's structure holds for the unrolled layer loop: the pool's leading
    axis is the attention layers, nothing of one layer's slice of it is made in
    the compiled decode program, and the donated pools are the result's own."""
    import re

    eng = engine_for(served)
    pool = eng._donated["cache"]["k"]
    layers, blocks, block_size = pool.shape[:3]
    assert pool.ndim == 4 and layers == served.config.attention_layers == 2
    compiled = eng._decode_jit.lower(
        eng._donated, eng._carried, served.params, eng._backend.device_tables()
    ).compile().as_text()
    slice_shape = rf"= \w+\[(1,)?{blocks},{block_size},[0-9,]*\]"
    made = [line.strip() for line in compiled.splitlines() if re.search(slice_shape, line)]
    assert not made, made[:3]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", compiled.split("\n", 1)[0]).group(1)
    assert re.search(r"\(0, \{\}, may-alias\)", aliases), aliases
    assert re.search(r"\(1, \{\}, may-alias\)", aliases), aliases


# -------------------------------------------------------------------- the counters
@pytest.fixture
def tracer(tmp_path):
    previous = tracing.get_tracer().config
    yield tracing.configure(TracingConfig(enabled=True, ring_capacity=4096, retain_s=60.0,
                                          dump_dir=str(tmp_path), max_dumps=1))
    tracing.configure(previous)


def test_expert_rows_ride_the_readback_ring(served, tracer):
    """Every step's rows an expert ride the ring beside its tokens and come
    out as scalars on the span that reads the step back: the decode steps over
    all four slots, the prefill over its bucket."""
    config = served.config
    eng = engine_for(served)
    eng.insert([5, 9, 17], max_new_tokens=6, pad_token_id=0)
    eng.insert([4, 4], max_new_tokens=4, pad_token_id=0)
    eng.drain()
    assert not eng._step_counters  # every entry was read with its ring entry
    spans = [sp for sp in tracer.spans(name="engine.readback") if "moe_expert_slots" in sp.attrs]
    by_kind = {kind: [sp.attrs for sp in spans if sp.attrs["kind"] == kind]
               for kind in ("prefill", "decode")}
    assert len(by_kind["prefill"]) == 2 and len(by_kind["decode"]) == eng.steps
    per_row = config.num_experts_per_tok * config.num_moe_layers
    slots = config.num_moe_layers * config.num_experts
    for attrs in by_kind["decode"]:
        assert attrs["moe_assignments"] == 4 * per_row and attrs["moe_expert_slots"] == slots
        assert 0 < attrs["moe_experts_touched"] <= min(slots, attrs["moe_assignments"])
        assert attrs["moe_load_max"] <= 4  # a slot reaches an expert at most once
    for attrs in by_kind["prefill"]:
        assert attrs["moe_assignments"] == 16 * per_row


# ----------------------------------------------------------------- the expert layer
def _expert_layer(seed=0, rows=24, bias_scale=0.02):
    config = Lfm2Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = create_lfm2(config, seed=seed).params["moe"]
    if bias_scale != 0.02:  # the scale the program draws it at
        params = {**params, "expert_bias": params["expert_bias"] * (bias_scale / 0.02)}
    x = jax.random.normal(jax.random.key(seed + 1), (rows, config.hidden_size), jnp.float32)
    return config, params, x


def _layer(config, params, x, index, **kw):
    return dropless_moe(
        x, params["router"]["kernel"][index], params["expert_bias"][index],
        params["experts"]["w1"], params["experts"]["w3"], params["experts"]["w2"],
        layer=index, num_selected=config.num_experts_per_tok, compute_dtype=jnp.float32, **kw)


@pytest.mark.parametrize("bias_scale", [0.0, 0.02, 1.0])
def test_expert_layer_matches_the_masked_loop(bias_scale):
    """Sorted rows and a grouped matmul against every expert over every row
    with a mask, without a bias, with the drawn one, and with one large enough
    to change most choices: chosen by ``s + b``, weighed by ``s``."""
    config, params, x = _expert_layer(bias_scale=bias_scale)
    weights = flat({"moe": params})
    for index in (0, config.num_moe_layers - 1):
        want = reference._experts(as_file(config), "float32", x, weights, index)
        got, rows = _layer(config, params, x, index)
        assert float(jnp.abs(got - want).max()) < F32_TOL
        assert int(rows.sum()) == x.shape[0] * config.num_experts_per_tok
    if bias_scale == 1.0:  # the bias moved the choice, and is not in the weights
        chosen, w = reference.routing(
            as_file(config), "float32", x, params["router"]["kernel"][0].astype(jnp.float32),
            params["expert_bias"][0].astype(jnp.float32))
        free, _ = reference.routing(
            {**as_file(config), "use_expert_bias": False}, "float32", x,
            params["router"]["kernel"][0].astype(jnp.float32), None)
        assert (np.sort(np.asarray(chosen), -1) != np.sort(np.asarray(free), -1)).any()
        assert float(jnp.abs(jnp.sum(w, -1) - 1.0).max()) < 1e-4


def test_four_shares_of_the_experts_add_up_to_the_whole_layer():
    """Each chip of four holds two of the eight experts, routes over all eight,
    and computes its own experts' part: the parts add up to the whole layer."""
    config, params, x = _expert_layer(seed=1)
    whole, rows = _layer(config, params, x, 1)
    held = config.num_experts // 4
    parts = []
    for share in range(4):
        first = share * held
        cut = {name: params["experts"][name][:, first : first + held] for name in ("w1", "w3", "w2")}
        part, share_rows = _layer(config, {**params, "experts": cut}, x, 1, first=first)
        assert np.array_equal(np.asarray(share_rows), np.asarray(rows))  # routing is over all
        parts.append(part)
    assert float(jnp.abs(sum(parts) - whole).max()) < F32_TOL
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    want = reference._experts(as_file(config), "float32", x, flat({"moe": params}), 1)
    assert float(jnp.abs(sum(parts) - want).max()) < F32_TOL


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="lists 2 layers"):
        Lfm2Config.tiny(layer_types=(CONV, ATTENTION))
    with pytest.raises(ValueError, match="unknown layer type"):
        Lfm2Config.tiny(layer_types=(CONV, ATTENTION, "sliding", CONV, CONV))
    only_conv = Lfm2Config.tiny(num_hidden_layers=2, layer_types=(CONV, CONV), num_dense_layers=0)
    family = only_conv.serving_family()
    assert (family.kv_layers, family.recurrent_layers, family.verify_step) == (0, 2, None)
