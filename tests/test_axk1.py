"""A.X-K1 (multi-head latent attention over one cached row a token a layer,
group-routed experts beside a shared one, a chip's share of both) against its
plain reference, at the tiny preset on the CPU.

The reference is the benchmark's own file, ``chipbench/reference/axk1.py``,
loaded by its path: one description of the model, kept where the chip run's
``correct`` reads it. It imports nothing of the benchmark or of the program.

Tolerances. In float32 the program and the reference do the same sums in another
order (the decode step a third order: absorbed): logits agree to ``F32_TOL``
(2e-5; read: 3e-6), and the same run with bfloat16 compute reads far over it,
which the store-by-store test checks, so that a path quietly computing lower
would fail it.
"""

import importlib.util
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import tracing
from accelerate_tpu.engine import ContinuousBatchingEngine
from accelerate_tpu.inference import generate
from accelerate_tpu.kvcache import PagedKVLayout, attend_step, pool_from_dense
from accelerate_tpu.models import axk1
from accelerate_tpu.models.axk1 import (
    AxK1Config,
    axk1_apply,
    axk1_decode_step,
    axk1_loss,
    axk1_prefill_at,
    create_axk1,
    yarn_inv_freq,
)
from accelerate_tpu.ops.attention import cache_attention
from accelerate_tpu.ops.moe import dropless_moe, route_sigmoid_topk
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.utils.dataclasses import ServingConfig, TracingConfig

F32_TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "chipbench", "reference", "axk1.py")
    spec = importlib.util.spec_from_file_location("axk1_reference_for_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load_reference()


def as_file(config: AxK1Config) -> dict:
    """The configuration as the benchmark's file states it: what the reference reads."""
    cfg = {f: getattr(config, f) for f in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "rms_norm_eps", "rope_theta", "rope_scaling", "vocab_size")}
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
    config = AxK1Config.tiny(param_dtype=jnp.float32, compute_dtype=dtype, **overrides)
    return config, create_axk1(config, seed=3)


def tokens(shape, seed=0, vocab=256):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0, vocab), np.int32)


def reference_logits(config, params, ids):
    one = jax.jit(lambda weights, row: reference.logits(as_file(config), weights, row))
    weights = flat(params)
    return np.stack([np.asarray(one(weights, jnp.asarray(row))) for row in ids])


# ---------------------------------------------------------------- the whole model
def test_the_tree_and_the_spec_name_the_same_leaves():
    for config in (AxK1Config.tiny(), AxK1Config.tiny(n_routed_experts=4, router_experts=16,
                                                      first_expert=8)):
        tree = flat(create_axk1(config, abstract=True).params)
        spec = {name: tuple(shape) for name, shape, _, _ in reference.weight_spec(as_file(config))}
        assert spec == {name: tuple(x.shape) for name, x in tree.items()}


@pytest.mark.parametrize("share", ["every_expert", "four_of_sixteen"])
def test_full_forward_matches_the_reference(share):
    held = {} if share == "every_expert" else dict(n_routed_experts=4, router_experts=16, first_expert=8)
    config, model = tiny(**held)
    ids = tokens((2, 20), seed=1)
    got = np.asarray(jax.jit(lambda p, x: axk1_apply(config, p, x))(model.params, jnp.asarray(ids)))
    want = reference_logits(config, model.params, ids)
    assert got.shape == (2, 20, config.vocab_size)
    assert np.abs(got - want).max() < F32_TOL
    assert np.abs(want).max() > 0.5  # logits of some size: the agreement is not of zeros


def test_the_loss_has_a_gradient():
    config, model = tiny()
    ids = jnp.asarray(tokens((2, 12), seed=2))

    def loss(params):
        return axk1_loss(model.bind(params), {"input_ids": ids})

    value, grads = jax.jit(jax.value_and_grad(loss))(model.params)
    assert np.isfinite(float(value)) and abs(float(value) - math.log(config.vocab_size)) < 1.0
    norms = {name: float(jnp.linalg.norm(g)) for name, g in flat(grads).items()}
    assert all(np.isfinite(n) for n in norms.values())
    for name in ("attn.0.kv_b_k.kernel", "attn.2.kv_b_v.kernel", "attn.1.kv_a.kernel",
                 "attn.0.q_b_nope.kernel", "attn.2.q_b_rope.kernel", "moe.router.kernel",
                 "moe.experts.w2", "moe.shared.w1.kernel", "lm_head.kernel"):
        assert norms[name] > 0, name


# ------------------------------------------------------- prefill, then every store
def _decode_through(config, params, ids, lengths, bucket, steps, cache_kind):
    """Right-padded prompts prefilled, then ``steps`` tokens through the store:
    ``(rows, steps + 1, V)`` logits, the prefill's first."""
    b, max_len, block = len(lengths), 32, 4
    padded = np.zeros((b, bucket), np.int32)
    for r, n in enumerate(lengths):
        padded[r, :n] = ids[r, :n]
    last = jnp.asarray(np.asarray(lengths) - 1)
    logits, cache, _ = jax.jit(lambda p, x, at: axk1_prefill_at(config, p, x, max_len, at))(
        params, jnp.asarray(padded), last)
    assert set(cache) == {"k"}
    layout = None
    if cache_kind != "dense":
        cache, tables = pool_from_dense(cache, block, quantized=False)
        layout = PagedKVLayout(tables, block, config.compute_dtype, config.cache_row_dim,
                               attention_impl=cache_kind)
    step = jax.jit(lambda p, c, t, pos: axk1_decode_step(config, p, c, t, pos, kv_layout=layout)[:2])
    out = [np.asarray(logits)]
    pos = np.asarray(lengths)
    for s in range(steps):
        token = jnp.asarray([[ids[r, pos[r]]] for r in range(b)])
        logits, cache = step(params, cache, token, jnp.asarray(pos))
        out.append(np.asarray(logits))
        pos = pos + 1
    return np.stack(out, axis=1)


@pytest.mark.parametrize("cache_kind", ["dense", "reference", "pallas"])
def test_prefill_then_decode_matches_the_full_forward(cache_kind):
    """Prompts shorter than their bucket (5 and 11 of 16) prefilled up-projected,
    then six tokens decoded absorbed over the dense arena, the paged pool gathered
    and committed, and the paged pool under the Pallas kernel: each against the
    reference's full forward."""
    config, model = tiny()
    ids = tokens((2, 24), seed=4)
    lengths, steps = [5, 11], 6
    want = reference_logits(config, model.params, ids)
    got = _decode_through(config, model.params, ids, lengths, 16, steps, cache_kind)
    for r, n in enumerate(lengths):
        assert np.abs(got[r] - want[r, n - 1: n + steps]).max() < F32_TOL, (cache_kind, r)
    if cache_kind == "pallas":  # the same through bfloat16 compute fails the tolerance
        low = AxK1Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)
        coarse = _decode_through(low, model.params, ids, lengths, 16, steps, cache_kind)
        assert np.abs(coarse[0] - want[0, 4: 5 + steps]).max() > 20 * F32_TOL


def test_the_cache_holds_one_row_a_token_a_layer_and_no_values():
    config, model = tiny()
    family = config.serving_family()
    latent = config.kv_lora_rank + config.qk_rope_head_dim
    # the row is stored in whole tiles of 128 lanes: 40 values here, 576 in 640 as published
    assert family.head_dim == config.cache_row_dim == 128 and family.kv_heads == 1
    assert AxK1Config.ax_k1().cache_row_dim == 640
    assert family.value_dim == config.kv_lora_rank and family.kv_layers == config.num_hidden_layers
    ids = jnp.asarray(tokens((1, 8), seed=5))
    _, cache, _ = jax.jit(lambda p: axk1_prefill_at(config, p, ids, 16, jnp.asarray([7])))(model.params)
    assert set(cache) == {"k"}
    assert cache["k"].shape == (config.num_hidden_layers, 1, 16, 1, config.cache_row_dim)
    assert float(jnp.abs(cache["k"][..., latent:]).max()) == 0.0  # zeros behind the row
    assert float(jnp.abs(cache["k"][:, :, :8, :, :latent]).min()) > 0.0
    for kv_cache in ("dense", "paged"):
        eng = ContinuousBatchingEngine(model, slots=2, max_len=16, prompt_bucket=8,
                                       kv_cache=kv_cache, block_size=4)
        state = eng._donated["cache"]
        assert set(state) == {"k"}, kv_cache
        per_token = config.num_hidden_layers * config.cache_row_dim * 4  # float32 here
        assert eng._backend.row_bytes() == per_token
        assert eng._backend.hbm_bytes() == state["k"].nbytes
    # per-head keys and values would be heads x (qk + v) a token a layer
    published = AxK1Config.ax_k1()
    per_head = published.num_attention_heads * (published.qk_head_dim + published.v_head_dim)
    assert per_head / (published.kv_lora_rank + published.qk_rope_head_dim) == pytest.approx(35.6, abs=0.1)


def test_absorbed_attention_is_up_projected_attention_on_the_same_rows():
    """One layer's attention both ways over the same cached rows: the sequence
    view's product order (keys and values up-projected a head) and the step
    view's (``W_kvb`` folded into the query, applied after the walk), query by
    query."""
    config, model = tiny()
    t, b, layer = 6, 2, 1
    h, rkv = config.num_attention_heads, config.kv_lora_rank
    key = jax.random.key(7)
    q_nope = jax.random.normal(key, (b, t, h, config.qk_nope_head_dim))
    q_rope = jax.random.normal(jax.random.fold_in(key, 1), (b, t, h, config.qk_rope_head_dim))
    row = axk1._padded_row(
        config, jax.random.normal(jax.random.fold_in(key, 2), (b, t, 1, rkv)),
        jax.random.normal(jax.random.fold_in(key, 3), (b, t, 1, config.qk_rope_head_dim)))
    kv_b = (model.params["attn"][str(layer)]["kv_b_k"]["kernel"],
            model.params["attn"][str(layer)]["kv_b_v"]["kernel"])
    up = axk1._SequenceView(config).attend(layer, q_nope, q_rope, row, *kv_b)
    arena = jnp.zeros((config.num_hidden_layers, b, t, 1, config.cache_row_dim))
    for pos in range(t):
        view = axk1._StepView(config, {"k": arena}, jnp.asarray(pos))
        out = view.attend(layer, q_nope[:, pos: pos + 1], q_rope[:, pos: pos + 1],
                          row[:, pos: pos + 1], *kv_b)
        arena = view.cache()["k"]
        assert float(jnp.abs(out[:, 0] - up[:, pos]).max()) < F32_TOL, pos
    assert float(jnp.abs(arena[layer] - row).max()) == 0.0  # what was cached is the rows


def test_the_seam_takes_a_latent_store_through_each_path():
    """``attend_step`` on ``(rows, None)``: the arena, the pool gathered, the pool
    under the kernel, all the one attention over a dense latent cache."""
    b, s, h, width, value, block = 2, 16, 4, 128, 32, 4
    key = jax.random.key(11)
    rows = jax.random.normal(key, (1, b, s, 1, width))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, h, width))
    new = jax.random.normal(jax.random.fold_in(key, 2), (b, 1, 1, width))
    pos = jnp.asarray([5, 11])
    written = jax.vmap(lambda c, n, p: c.at[p].set(n[0]))(rows[0], new, pos)
    want = cache_attention(q, written, written[..., :value], pos, scale=0.2)
    assert want.shape == (b, 1, h, value)
    out, (arena, none) = attend_step(None, (rows, None), 0, q, new, None, pos, scale=0.2,
                                     value_dim=value)
    assert none is None and float(jnp.abs(out - want).max()) < 1e-6
    assert float(jnp.abs(arena[0] - written).max()) == 0.0
    pool, tables = pool_from_dense({"k": rows}, block, quantized=False)
    for impl in ("reference", "pallas"):
        layout = PagedKVLayout(tables, block, jnp.float32, width, attention_impl=impl)
        out, (kept, none) = attend_step(layout, (pool["k"], None), 0, q, new, None, pos,
                                        scale=0.2, value_dim=value)
        assert none is None and float(jnp.abs(out - want).max()) < 1e-5, impl
        assert float(jnp.abs(layout.view(kept, 0) - written).max()) == 0.0, impl


# ------------------------------------------------------------------- the routing
def plain_router(scores, n_group, topk_group, k, scale):
    """Group-limited choice in plain Python, a row at a time: ``(chosen, weights)``.
    Ties go to the lower index, groups and experts alike."""
    chosen, weights = [], []
    for row in np.asarray(scores, np.float64):
        size = len(row) // n_group
        groups = [row[g * size: (g + 1) * size] for g in range(n_group)]
        group_score = [sum(sorted(g, reverse=True)[:2]) for g in groups]
        kept = sorted(range(n_group), key=lambda g: (-group_score[g], g))[:topk_group]
        allowed = [e for e in range(len(row)) if e // size in kept]
        picked = sorted(allowed, key=lambda e: (-row[e], e))[:k]
        total = sum(row[e] for e in picked) + 1e-20
        chosen.append(picked)
        weights.append([row[e] / total * scale for e in picked])
    return np.asarray(chosen), np.asarray(weights)


def test_group_limited_choice_matches_a_plain_router_on_the_groups_edges():
    """Rows built to sit on the edges: a group whose best expert is the best of all
    but whose two best sum to less than another's; two groups tied exactly; the
    k-th best expert inside a group that is dropped; and random rows besides."""
    e, n_group, topk_group, k = 16, 4, 2, 4
    rng = np.random.default_rng(0)
    base = rng.uniform(0.05, 0.3, (8, e))
    base[0, 0], base[0, 1:4], base[0, 4:6], base[0, 8:10] = 0.95, 0.05, 0.6, 0.55  # group 0: one star, loses on its pair
    base[1, 0:2], base[1, 4:6], base[1, 8:10] = 0.7, 0.7, 0.5  # groups 0 and 1 tied, 2 below
    base[2, 12], base[2, 0:2], base[2, 4:6] = 0.9, 0.8, 0.75  # expert 12 is third best of all, its group is out
    base[3, :] = 0.5  # everything tied: the lowest groups and experts
    logits = np.log(base / (1 - base))  # sigmoid(logits) = base
    x = jnp.eye(8, dtype=jnp.float32)  # row i of the router is row i's logits
    experts, weights = route_sigmoid_topk(
        x, jnp.asarray(logits, jnp.float32), None, k, norm_topk=True, norm_eps=1e-20,
        scale=2.5, n_group=n_group, topk_group=topk_group)
    want_experts, want_weights = plain_router(base, n_group, topk_group, k, 2.5)
    assert np.array_equal(np.asarray(experts), want_experts)
    assert np.abs(np.asarray(weights) - want_weights).max() < 1e-5
    assert sorted(want_experts[0] // 4) == [1, 1, 2, 2]  # the star's group lost
    assert sorted(want_experts[1] // 4) == [0, 0, 1, 1] and 12 not in want_experts[2]
    assert want_experts[3].tolist() == [0, 1, 2, 3]
    assert np.allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)
    # without groups the same function is the plain top-k: expert 12 is back
    free, _ = route_sigmoid_topk(x, jnp.asarray(logits, jnp.float32), None, k)
    assert 12 in np.asarray(free)[2]
    # and the reference's own routing agrees on the random rows too
    cfg = dict(n_group=n_group, topk_group=topk_group, num_experts_per_tok=k,
               norm_topk_prob=True, routed_scaling_factor=2.5)
    ref_experts, ref_weights = reference.routing(cfg, "float32", x, jnp.asarray(logits, jnp.float32))
    assert np.array_equal(np.asarray(ref_experts), want_experts)
    assert np.abs(np.asarray(ref_weights) - want_weights).max() < 1e-5


def test_yarn_frequencies_and_scale_against_numbers_written_out_by_hand():
    published = AxK1Config.ax_k1()
    inv = yarn_inv_freq(64, 10000.0, published.rope_scaling)
    assert inv.shape == (32,)
    # dimension_of(32 turns) = 64 ln(4096 / (64 pi)) / (2 ln 10000) = 10.47 -> 10;
    # dimension_of(1 turn) = 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], plain[:11], rtol=1e-12)  # turn fast: as they were
    assert np.allclose(inv[23:], plain[23:] / 32.0, rtol=1e-12)  # turn slow: over the factor
    assert inv[16] == pytest.approx(plain[16] * (1 - 6 / 13) + plain[16] / 32 * (6 / 13), rel=1e-12)
    assert inv[0] == 1.0 and inv[31] == pytest.approx(10000.0 ** (-31 / 32) / 32)
    assert inv[16] == pytest.approx(0.0055289, rel=1e-4)  # 0.01 * (7/13 + 6/13/32)
    # m = 0.1 ln 32 + 1 = 1.346574; scale = 192^-0.5 m^2 = 0.0721688 x 1.813260
    assert published.softmax_scale == pytest.approx(0.130861, rel=1e-5)
    assert published.rope_amplitude == 1.0
    assert np.allclose(reference.inv_freq(as_file(published)), inv, rtol=1e-12)
    assert reference.softmax_scale(as_file(published)) == pytest.approx(published.softmax_scale)


# --------------------------------------------------------------------- the share
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_layer():
    """Four chips hold four of the sixteen experts each, route over all sixteen
    and compute their own experts' part; every chip computes the shared expert
    alike. The routed parts plus the shared expert ONCE are the uncut layer, by
    the program and by the reference given the same shares."""
    config, model = tiny()
    p = model.params["moe"]
    index = 1
    x = jax.random.normal(jax.random.key(9), (24, config.hidden_size), jnp.float32)
    whole_view = axk1._SequenceView(config)
    whole = axk1._experts(config, p, index, x[None], whole_view)[0]
    shared = axk1._swiglu(config, x[None], p["shared"], index)[0]
    held = config.n_routed_experts // 4
    parts = []
    for share in range(4):
        first = share * held
        cut_config = AxK1Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                                     n_routed_experts=held, router_experts=16, first_expert=first)
        cut = {**p, "experts": {n: p["experts"][n][:, first: first + held] for n in ("w1", "w3", "w2")}}
        view = axk1._SequenceView(cut_config)
        part = axk1._experts(cut_config, cut, index, x[None], view)[0]
        assert np.array_equal(np.asarray(view.expert_rows[0]), np.asarray(whole_view.expert_rows[0]))
        want = reference._experts(as_file(cut_config), "float32", x, flat({"moe": cut}), index)
        assert float(jnp.abs(part - want).max()) < F32_TOL, share
        parts.append(part - shared)  # a chip's routed part
        assert float(jnp.abs(parts[-1]).max()) > 0
    assert float(jnp.abs(sum(parts) + shared - whole).max()) < F32_TOL
    uncut = reference._experts(as_file(config), "float32", x, flat({"moe": p}), index)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < F32_TOL
    # counted four times the shared expert is another layer
    assert float(jnp.abs(sum(parts) + 4 * shared - uncut).max()) > 100 * F32_TOL


@pytest.mark.parametrize("rows,held", [(1024, 4), (64, 4), (64, 16)])
def test_the_head_of_the_sorted_pairs_gives_what_all_of_them_give(rows, held):
    """With a share of the experts held the grouped matmuls take the head of the
    sorted pairs (``_head_pairs``), and all of them when a batch crowds onto the
    held experts: the same result either way, and the same as every pair."""
    from accelerate_tpu.ops import moe

    config = AxK1Config.tiny(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                             n_routed_experts=held, router_experts=16, first_expert=0)
    p = create_axk1(config, seed=2).params["moe"]
    x = jax.random.normal(jax.random.key(3), (rows, config.hidden_size), jnp.float32)
    k = config.num_experts_per_tok
    head = moe._head_pairs(rows * k, held, 16)
    # 4,096 pairs with 4 of 16 held: the first 2,048; 256 pairs: 128; every expert held: all
    assert head == {(1024, 4): 2048, (64, 4): 128, (64, 16): 256}[rows, held]
    x = x.at[:, 0].set(1.0)  # a column the crowded router below can lean on

    def layer(x, router):
        return dropless_moe(
            x, router, None, p["experts"]["w1"], p["experts"]["w3"], p["experts"]["w2"], layer=0,
            num_selected=k, norm_eps=1e-20, scale=2.5, n_group=4, topk_group=2,
            compute_dtype=jnp.float32)

    router = p["router"]["kernel"][0]
    got, counted = jax.jit(layer)(x, router)
    want = reference._experts({**as_file(config), "n_shared_experts": 0}, "float32", x,
                              flat({"moe": p}), 0)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert int(counted.sum()) == rows * k
    if rows == 1024:  # a router that sends every row to the held experts: the other branch
        crowded = router.at[0, :4].add(50.0)
        got, counted = jax.jit(layer)(x, crowded)
        assert int(counted[:4].sum()) >= head
        want = reference._experts({**as_file(config), "n_shared_experts": 0}, "float32", x,
                                  {**flat({"moe": p}), "moe.router.kernel": p["router"]["kernel"].at[0].set(crowded)}, 0)
        assert float(jnp.abs(got - want).max()) < 5 * F32_TOL


# ------------------------------------------------------------------- the engine
def engine_for(model, **kw):
    kw = {**dict(slots=4, max_len=48, prompt_bucket=16, kv_cache="paged", block_size=4,
                 attention_impl="pallas", readback_lag=2), **kw}
    return ContinuousBatchingEngine(model, **kw)


@pytest.fixture(scope="module")
def served():
    """The tiny model in bfloat16 compute, four of sixteen experts held."""
    config = AxK1Config.tiny(n_routed_experts=4, router_experts=16, first_expert=4)
    return create_axk1(config, seed=3)


@pytest.fixture(scope="module")
def generated():
    config, model = tiny()
    prompts = tokens((2, 9), seed=6)
    return model, prompts, np.asarray(generate(model, prompts, max_new_tokens=5))


@pytest.mark.parametrize("kv_cache,impl", [("dense", "reference"), ("paged", "reference"),
                                           ("paged", "pallas")])
def test_engine_tokens_equal_generate(generated, kv_cache, impl):
    model, prompts, want = generated
    eng = engine_for(model, kv_cache=kv_cache, attention_impl=impl)
    occs = [eng.insert(p, max_new_tokens=5, pad_token_id=0) for p in prompts]
    eng.drain()
    for occ, row in zip(occs, want):
        assert occ.output_row().tolist() == row.tolist()
    assert eng.stats()["programs"] == {"prefill_insert": 1, "decode_step": 1}


def test_what_is_not_carried_is_refused_by_name(served):
    with pytest.raises(ValueError, match=r"speculative decoding needs a verify_step.*latent cache"):
        engine_for(served, spec="ngram")
    with pytest.raises(ValueError, match=r"chunked prefill needs a verify_step"):
        engine_for(served, prefill_chunk=8)
    with pytest.raises(ValueError, match=r"kv_host_tier_bytes cannot serve a latent cache"):
        engine_for(served, host_tier_bytes=1 << 20)
    with pytest.raises(ValueError, match=r"kv_cache=paged_int8 cannot serve a latent cache"):
        engine_for(served, kv_cache="paged_int8")


def test_a_token_id_outside_the_slice_is_refused(served):
    """The vocabulary held here is a slice: an id past it would gather another
    token's row in silence."""
    eng = engine_for(served)
    vocab = served.config.vocab_size
    with pytest.raises(ValueError, match=rf"outside the vocabulary served here \(0 \.\. {vocab - 1}\)"):
        eng.insert([1, vocab, 3], max_new_tokens=2)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        eng.insert([-1, 2], max_new_tokens=2)
    cfg = ServingConfig(mode="continuous", kv_cache="paged", attention_impl="pallas",
                        engine_slots=2, engine_max_len=32, engine_prompt_bucket=8,
                        engine_block_size=4)
    with InferenceServer(served, cfg) as server:  # refused at the door, before any queue
        with pytest.raises(ValueError, match="outside the vocabulary"):
            server.submit([5, vocab + 7], max_new_tokens=2)
    eng.validate_prompt(np.asarray([0, vocab - 1]))  # the slice's own ends are served


@pytest.fixture
def tracer(tmp_path):
    previous = tracing.get_tracer().config
    yield tracing.configure(TracingConfig(enabled=True, ring_capacity=4096, retain_s=60.0,
                                          dump_dir=str(tmp_path), max_dumps=1))
    tracing.configure(previous)


def test_held_experts_rows_and_the_row_bytes_ride_the_spans(served, tracer):
    """Every step's counters are over the experts held here, with the pairs whose
    expert another chip holds beside them; the decode step's span says what a
    position holds in the cache."""
    config = served.config
    eng = engine_for(served)
    eng.insert([5, 9, 17], max_new_tokens=6, pad_token_id=0)
    eng.insert([4, 4], max_new_tokens=4, pad_token_id=0)
    eng.drain()
    assert not eng._step_counters
    spans = [sp for sp in tracer.spans(name="engine.readback") if "moe_expert_slots" in sp.attrs]
    by_kind = {kind: [sp.attrs for sp in spans if sp.attrs["kind"] == kind]
               for kind in ("prefill", "decode")}
    assert len(by_kind["prefill"]) == 2 and len(by_kind["decode"]) == eng.steps
    per_row = config.num_experts_per_tok * config.num_moe_layers
    slots = config.num_moe_layers * config.n_routed_experts  # 2 x 4 held
    for kind, rows in (("decode", 4), ("prefill", 16)):
        for attrs in by_kind[kind]:
            assert attrs["moe_expert_slots"] == slots == 8
            assert attrs["moe_assignments"] + attrs["moe_rows_elsewhere"] == rows * per_row
            assert attrs["moe_experts_touched"] <= min(slots, attrs["moe_assignments"])
            assert attrs["moe_load_max"] <= rows
    assert sum(a["moe_assignments"] for a in by_kind["prefill"]) > 0
    assert sum(a["moe_rows_elsewhere"] for a in by_kind["prefill"]) > 0
    steps = tracer.spans(name="engine.decode_step")
    # 3 layers x 128 stored values x 2 bytes (bfloat16 here), one leaf
    assert steps and all(sp.attrs["kv_row_bytes"] == 3 * 128 * 2 for sp in steps)
