"""LongCat-Flash (shortcut-connected MoE double layers, two latent (MLA) sublayers a
layer, softmax-routed experts beside zero-compute ones, a chip's share of them)
against its plain reference, at the tiny preset on the CPU.

The reference is the benchmark's own file, ``chipbench/reference/longcat.py``,
loaded by its path: one description of the model, kept where the chip run's
``correct`` reads it.

Tolerances. In float32 the program and the reference do the same sums in another
order (the decode step a third order: absorbed): logits agree to ``F32_TOL`` (2e-5,
A.X-K1's, whose latent sublayer this is; read here: under 4e-6), and the same run
with bfloat16 compute reads far over it, which the store-by-store test checks, so
that a path quietly computing lower would fail it. A part of the model left out (the
ScMoE order, the latent scales) moves the logits by a hundred times the tolerance or
more, which the last tests check.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.engine import ContinuousBatchingEngine
from accelerate_tpu.kvcache import PagedKVLayout, pool_from_dense
from accelerate_tpu.models import longcat
from accelerate_tpu.models.longcat import (
    LongcatFlashConfig,
    create_longcat,
    longcat_apply,
    longcat_decode_step,
    longcat_prefill_at,
)
from accelerate_tpu.ops.moe import dropless_moe, route_sigmoid_topk

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "chipbench", "reference", "longcat.py")
    spec = importlib.util.spec_from_file_location("longcat_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load_reference()

HELD = dict(n_routed_experts=4, router_experts=24, first_expert=4)  # 4 of 16 routed, 8 zero


def as_file(config: LongcatFlashConfig) -> dict:
    """The configuration as the benchmark's file states it: what the reference reads."""
    cfg = {f: getattr(config, f) for f in (
        "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
        "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
        "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor",
        "n_routed_experts", "zero_expert_num", "moe_topk", "rms_norm_eps", "rope_theta")}
    cfg["program_keys"] = {"router_experts": config.router_experts,
                           "first_expert": config.first_expert}
    return cfg


def flat(tree, prefix="") -> dict:
    out = {}
    for key, value in tree.items():
        name = f"{prefix}.{key}" if prefix else key
        out.update(flat(value, name) if isinstance(value, dict) else {name: value})
    return out


def tiny(dtype=jnp.float32, **overrides):
    config = LongcatFlashConfig.tiny(param_dtype=jnp.float32, compute_dtype=dtype, **overrides)
    return config, create_longcat(config, seed=3)


def tokens(shape, seed=0, vocab=256):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0, vocab), np.int32)


def reference_logits(config, params, ids):
    one = jax.jit(lambda weights, row: reference.logits(as_file(config), weights, row))
    weights = flat(params)
    return np.stack([np.asarray(one(weights, jnp.asarray(row))) for row in ids])


def forward(config, params, ids):
    return np.asarray(jax.jit(lambda p, x: longcat_apply(config, p, x))(params, jnp.asarray(ids)))


# ---------------------------------------------------------------- the whole model
@pytest.mark.parametrize("share", ["every_expert", "four_of_sixteen"])
def test_full_forward_matches_the_reference(share):
    config, model = tiny(**({} if share == "every_expert" else HELD))
    tree = flat(model.params)
    spec = {name: tuple(shape) for name, shape, _, _ in reference.weight_spec(as_file(config))}
    assert spec == {name: tuple(x.shape) for name, x in tree.items()}
    ids = tokens((2, 20), seed=1)
    got, want = forward(config, model.params, ids), reference_logits(config, model.params, ids)
    assert got.shape == (2, 20, config.vocab_size)
    assert np.abs(got - want).max() < F32_TOL
    assert np.abs(want).max() > 0.5  # logits of some size: the agreement is not of zeros


def _decode_through(config, params, ids, lengths, bucket, steps, cache_kind):
    """Right-padded prompts prefilled, then ``steps`` tokens through the store:
    ``(rows, steps + 1, V)`` logits, the prefill's first."""
    b, max_len, block = len(lengths), 32, 4
    padded = np.zeros((b, bucket), np.int32)
    for r, n in enumerate(lengths):
        padded[r, :n] = ids[r, :n]
    last = jnp.asarray(np.asarray(lengths) - 1)
    logits, cache, _ = jax.jit(lambda p, x, at: longcat_prefill_at(config, p, x, max_len, at))(
        params, jnp.asarray(padded), last)
    assert set(cache) == {"k"} and cache["k"].shape[0] == 2 * config.num_layers
    layout = None
    if cache_kind != "dense":
        cache, tables = pool_from_dense(cache, block, quantized=False)
        layout = PagedKVLayout(tables, block, config.compute_dtype, config.cache_row_dim,
                               attention_impl=cache_kind)
    step = jax.jit(lambda p, c, t, pos: longcat_decode_step(config, p, c, t, pos, kv_layout=layout)[:2])
    out = [np.asarray(logits)]
    pos = np.asarray(lengths)
    for _ in range(steps):
        token = jnp.asarray([[ids[r, pos[r]]] for r in range(b)])
        logits, cache = step(params, cache, token, jnp.asarray(pos))
        out.append(np.asarray(logits))
        pos = pos + 1
    return np.stack(out, axis=1)


@pytest.mark.parametrize("cache_kind", ["dense", "reference", "pallas"])
def test_prefill_then_decode_matches_the_full_forward(cache_kind):
    """Prompts shorter than their bucket (5 and 11 of 16) prefilled up-projected,
    then four tokens decoded absorbed over the dense arena, the paged pool gathered
    and committed, and the paged pool under the Pallas kernel (interpreted): each
    against the reference's full forward."""
    config, model = tiny(**HELD)
    ids = tokens((2, 24), seed=4)
    lengths, steps = [5, 11], 4
    want = reference_logits(config, model.params, ids)
    got = _decode_through(config, model.params, ids, lengths, 16, steps, cache_kind)
    for r, n in enumerate(lengths):
        assert np.abs(got[r] - want[r, n - 1: n + steps]).max() < F32_TOL, (cache_kind, r)
    if cache_kind == "dense":  # the same through bfloat16 compute fails the tolerance
        low = LongcatFlashConfig.tiny(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, **HELD)
        coarse = _decode_through(low, model.params, ids, lengths, 16, steps, cache_kind)
        assert np.abs(coarse[0] - want[0, 4: 5 + steps]).max() > 20 * F32_TOL


def test_the_engine_serves_the_models_greedy_tokens():
    """The normal path, paged pool under the decode kernel: every token the engine
    makes greedily is the full forward's best at its position."""
    config, model = tiny(num_layers=1)  # its two sublayers, two rows of the cache
    prompts = tokens((2, 9), seed=6)
    eng = ContinuousBatchingEngine(model, slots=4, max_len=48, prompt_bucket=16,
                                   kv_cache="paged", block_size=4, attention_impl="pallas")
    occs = [eng.insert(p, max_new_tokens=5, pad_token_id=0) for p in prompts]
    eng.drain()
    rows = np.stack([occ.output_row() for occ in occs])
    assert rows.shape == (2, 14) and np.array_equal(rows[:, :9], prompts)
    best = forward(config, model.params, rows).argmax(-1)
    assert np.array_equal(best[:, 8:13], rows[:, 9:])
    assert eng.stats()["programs"] == {"prefill_insert": 1, "decode_step": 1}
    # two latent sublayers a layer, one row each, float32 here
    assert eng._backend.row_bytes() == 2 * config.num_layers * config.cache_row_dim * 4


# ------------------------------------------------------------------- the experts
def _moe(config, p, x, **kw):
    """One expert layer (layer 1) of the program over rows ``x``: ``(out, rows)``."""
    return dropless_moe(
        x, p["router"]["kernel"][1], p["router"]["e_score_correction_bias"][1],
        p["experts"]["w1"], p["experts"]["w3"], p["experts"]["w2"], layer=1,
        first=config.first_expert, num_selected=config.moe_topk, norm_topk=False,
        scale=config.routed_scaling_factor, scoring="softmax",
        zero_experts=config.zero_expert_num, compute_dtype=jnp.float32, **kw)


def test_the_shares_routed_parts_and_the_identity_part_once_add_up_to_the_layer():
    """Four chips hold four of the sixteen routed experts each, route over all 24
    outputs and compute their own experts' part; every chip adds the identity
    experts' part alike. The routed parts plus the identity part ONCE are the uncut
    reference's expert block."""
    config, model = tiny()
    p = model.params["moe"]
    x = jax.random.normal(jax.random.key(9), (24, config.hidden_size), jnp.float32)
    chosen, weights = reference.routing(
        as_file(config), "float32", x, p["router"]["kernel"][1], p["router"]["e_score_correction_bias"][1])
    identity = jnp.sum(jnp.where(chosen >= 16, weights, 0.0), axis=-1)[:, None] * x
    assert float(jnp.abs(identity).max()) > 0  # some rows chose an identity
    parts = []
    for share in range(4):
        cut_config = LongcatFlashConfig.tiny(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                                             n_routed_experts=4, router_experts=24,
                                             first_expert=4 * share)
        cut = {**p, "experts": {n: p["experts"][n][:, 4 * share: 4 * share + 4] for n in ("w1", "w3", "w2")}}
        part, _ = _moe(cut_config, cut, x)
        want = reference._experts(as_file(cut_config), "float32", x, flat({"moe": cut}), 1)
        assert float(jnp.abs(part - want).max()) < F32_TOL, share
        parts.append(part - identity)  # a chip's routed part
        assert float(jnp.abs(parts[-1]).max()) > 0
    uncut = reference._experts(as_file(config), "float32", x, flat({"moe": p}), 1)
    assert float(jnp.abs(sum(parts) + identity - uncut).max()) < F32_TOL
    # counted four times the identity part is another layer
    assert float(jnp.abs(sum(parts) + 4 * identity - uncut).max()) > 100 * F32_TOL


def test_zero_compute_pairs_reach_no_group_and_add_their_weighted_row(monkeypatch):
    """A selection bias that sends every row to the identity experts: no grouped
    matmul gets a row, and the layer returns ``sum w * x``; the pairs are counted."""
    from accelerate_tpu.ops import moe

    config, model = tiny()
    p = model.params["moe"]
    x = jax.random.normal(jax.random.key(2), (16, config.hidden_size), jnp.float32)
    away = p["router"]["e_score_correction_bias"].at[1, :16].set(-1.0)  # routed experts never win
    p = {**p, "router": {**p["router"], "e_score_correction_bias": away}}
    sizes = []  # the rows each held expert's group is handed
    visits = moe.group_visits
    monkeypatch.setattr(moe, "group_visits", lambda s, *a: sizes.append(s) or visits(s, *a))
    out, rows = _moe(config, p, x)
    assert int(rows[:16].sum()) == 0 and int(rows[16:].sum()) == 16 * config.moe_topk
    assert sizes and all(int(jnp.sum(s)) == 0 for s in sizes)
    scores = jax.nn.softmax(x @ p["router"]["kernel"][1], axis=-1)
    top = jax.lax.top_k(scores[:, 16:], config.moe_topk)[0]
    want = jnp.sum(top, axis=-1, keepdims=True) * config.routed_scaling_factor * x
    assert float(jnp.abs(out - want).max()) < F32_TOL


def test_softmax_routing_chooses_by_score_and_bias_and_weighs_by_score():
    """Softmax scores over all outputs; a selection bias that lifts experts the
    scores alone would not choose; weights are the scores times 6, unnormalised."""
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 1, (8, 24)).astype(np.float32)
    bias = np.zeros(24, np.float32)
    bias[[3, 17, 22]] = 1.0  # far above any softmax score
    x = jnp.eye(8, dtype=jnp.float32)  # row i of the router is row i's logits
    experts, weights = route_sigmoid_topk(x, jnp.asarray(logits), jnp.asarray(bias), 4,
                                          norm_topk=False, scale=6.0, scoring="softmax")
    scores = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    for row in range(8):
        by = scores[row] + bias
        want = sorted(range(24), key=lambda e: (-by[e], e))[:4]
        assert np.asarray(experts[row]).tolist() == want
        assert {3, 17, 22} <= set(want)
        assert np.allclose(np.asarray(weights[row]), 6.0 * scores[row][want], atol=1e-6)
    assert float(jnp.sum(weights, axis=-1).max()) < 6.0  # not renormalised
    ref_experts, ref_weights = reference.routing(
        {"moe_topk": 4, "routed_scaling_factor": 6.0}, "float32", x, jnp.asarray(logits),
        jnp.asarray(bias))
    assert np.array_equal(np.asarray(ref_experts), np.asarray(experts))
    assert np.abs(np.asarray(ref_weights) - np.asarray(weights)).max() < 1e-6


# ------------------------------------------------------ what a slip would look like
def test_the_scmoe_order_is_what_the_reference_holds_the_program_to(monkeypatch):
    """The experts read ``u``, the first half's normed state. A block that feeds
    them the third state ``h3`` instead is another model, and the comparison
    with the reference says so."""
    config, model = tiny(**HELD)
    ids = tokens((1, 16), seed=7)
    want = reference_logits(config, model.params, ids)
    assert np.abs(forward(config, model.params, ids) - want).max() < F32_TOL
    from accelerate_tpu.models.llama import rms_norm

    def late_block(config, params, layer, x, view):
        eps = config.rms_norm_eps

        def attention(x, sub):
            p = params["attn"][str(2 * layer + sub)]
            return x + longcat.mla_attention(config, p, 2 * layer + sub,
                                             rms_norm(x, p["input_norm"]["scale"], eps), view)

        first, second = params["ffn"][str(2 * layer)], params["ffn"][str(2 * layer + 1)]
        h1 = attention(x, 0)
        u = rms_norm(h1, first["norm"]["scale"], eps)
        h3 = attention(h1 + longcat.swiglu(config, u, first), 1)
        late = rms_norm(h3, second["norm"]["scale"], eps)
        m = longcat._experts(config, params["moe"], layer, late, view)
        return h3 + longcat.swiglu(config, late, second) + m

    monkeypatch.setattr(longcat, "_block", late_block)
    assert np.abs(forward(config, model.params, ids) - want).max() > 100 * F32_TOL


def test_the_latent_scales_are_what_the_reference_holds_the_program_to():
    """Both published switches on: ``sqrt(d / q_lora_rank)`` on the query latent,
    ``sqrt(d / kv_lora_rank)`` on the key-value latent. A program that leaves
    either out is another model."""
    config, model = tiny(**HELD)
    assert config.latent_scales == pytest.approx((np.sqrt(64 / 32), np.sqrt(64 / 32)))
    ids = tokens((1, 16), seed=8)
    want = reference_logits(config, model.params, ids)
    for off in (dict(mla_scale_q_lora=False), dict(mla_scale_kv_lora=False)):
        unscaled = LongcatFlashConfig.tiny(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                                           **HELD, **off)
        assert np.abs(forward(unscaled, model.params, ids) - want).max() > 100 * F32_TOL, off
