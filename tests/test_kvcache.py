"""Paged KV-cache subsystem suite (docs/serving.md "Paged KV & prefix
caching"):

* host allocator units — blocks_needed math, free/active/cached accounting,
  COW refcounts (owner-retires-first included), LRU eviction, capacity
  errors;
* int8 KV quantization — per-position roundtrip error bound and bitwise
  determinism;
* ``paged_attention`` reference op parity against dense attention;
* dense↔paged bitwise-greedy parity through the engine AND the real
  :class:`InferenceServer`, including slot reuse under a deliberately tiny
  (block-recycling) pool;
* admission gating on free blocks + the typed ``ValueError`` naming the
  paged knobs;
* the "exactly two compiled programs" property for a paged engine;
* stats/metrics satellites: pool HBM bytes, live-vs-reserved utilization,
  prefix-cache hit rate (engine stats and serving gauges);
* static ``generate(kv_backend=...)`` parity and the ``ServingConfig``
  validation surface.

Engines compile two programs each, so tests share per-shape engines via a
module-scoped cache (``reset()`` restores a pristine pool between tests).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from accelerate_tpu.engine import ContinuousBatchingEngine
from accelerate_tpu.inference import generate
from accelerate_tpu.kvcache import (
    PagedBlockPool,
    PagedKVBackend,
    kv_dequantize,
    kv_quantize,
    make_kv_backend,
)
from accelerate_tpu.models.llama import LlamaConfig, create_llama
from accelerate_tpu.ops.attention import dot_product_attention, paged_attention
from accelerate_tpu.serving import InferenceServer
from accelerate_tpu.utils.dataclasses import ServingConfig


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    return create_llama(cfg, seed=0)


_ENGINES: dict = {}


@pytest.fixture
def get_engine(model):
    """Engine per shape+backend, cached across the module so each config
    pays its two compiles once; reset before handout."""

    def _get(slots=2, max_len=64, prompt_bucket=16, readback_lag=2,
             kv_cache="paged", block_size=8, pool_blocks=None):
        key = (slots, max_len, prompt_bucket, readback_lag,
               kv_cache, block_size, pool_blocks)
        eng = _ENGINES.get(key)
        if eng is None:
            eng = _ENGINES[key] = ContinuousBatchingEngine(
                model, slots=slots, max_len=max_len,
                prompt_bucket=prompt_bucket, readback_lag=readback_lag,
                kv_cache=kv_cache, block_size=block_size,
                pool_blocks=pool_blocks,
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


# ---------------------------------------------------------- allocator units
def _pool(num_blocks=9, block_size=4, slots=3, blocks_per_row=4):
    return PagedBlockPool(
        num_blocks=num_blocks, block_size=block_size, slots=slots,
        blocks_per_row=blocks_per_row,
    )


def test_pool_blocks_needed_covers_final_decode_write():
    pool = _pool(block_size=4)
    # budget tokens end at position prompt+budget-1; a done slot keeps
    # re-writing that frozen position, so it must own its block
    assert pool.blocks_needed(4, 4) == 2
    assert pool.blocks_needed(5, 4) == 3
    assert pool.blocks_needed(1, 2) == 1


def test_pool_acquire_release_roundtrip_and_null_row():
    pool = _pool()
    prompt = np.arange(1, 7, dtype=np.int32)  # 6 tokens, bs=4 -> 1 full block
    row, shared = pool.acquire(0, prompt, budget=3)
    assert shared == 0
    assert row.shape == (4,)
    used = pool.blocks_needed(6, 3)
    assert (row[:used] != 0).all() and (row[used:] == 0).all()
    assert pool.active_blocks() == used
    pool.release(0)
    # row resets to the null block so ghost-slot writes land in the sink
    assert (pool.tables[0] == 0).all()
    # the full prompt block registered -> cached; the partial block freed
    assert pool.stats()["blocks_cached"] == 1
    assert pool.free_blocks() == pool.num_blocks - 1


def test_pool_cow_shares_full_prompt_blocks():
    pool = _pool(block_size=4)
    prompt = np.arange(1, 11, dtype=np.int32)  # 10 tokens -> 2 full blocks
    row_a, shared_a = pool.acquire(0, prompt, budget=2)
    row_b, shared_b = pool.acquire(1, prompt, budget=2)
    assert shared_a == 0 and shared_b == 2
    np.testing.assert_array_equal(row_a[:2], row_b[:2])  # shared prefix ids
    assert row_a[2] != row_b[2]  # private tail blocks differ
    assert pool._ref[row_a[0]] == 2
    # diverging prompt shares only the depth-1 block
    other = prompt.copy()
    other[5] += 1
    _, shared_c = pool.acquire(2, other, budget=2)
    assert shared_c == 1


def test_pool_cow_owner_retires_first_keeps_serving_hits():
    pool = _pool(block_size=4)
    prompt = np.arange(1, 10, dtype=np.int32)  # 2 full blocks + partial
    row_a, _ = pool.acquire(0, prompt, budget=2)
    pool.release(0)  # owner gone; registered blocks park in the cached tier
    assert pool.stats()["blocks_cached"] == 2
    row_b, shared = pool.acquire(1, prompt, budget=2)
    assert shared == 2
    np.testing.assert_array_equal(row_a[:2], row_b[:2])
    assert pool.stats()["blocks_cached"] == 0  # revived cached -> active
    assert pool._ref[row_b[0]] == 1


def test_pool_lru_eviction_and_capacity_errors():
    pool = _pool(num_blocks=5, block_size=4, slots=2, blocks_per_row=3)
    a = np.arange(1, 5, dtype=np.int32)
    b = np.arange(10, 14, dtype=np.int32)
    pool.acquire(0, a, budget=4)  # 2 blocks (1 registered)
    pool.acquire(1, b, budget=4)  # 2 blocks -> pool fully allocated
    assert not pool.can_admit(a, budget=4)  # a's hit is active, not evictable
    with pytest.raises(RuntimeError, match="no free KV blocks"):
        pool.acquire(0, np.arange(20, 24, dtype=np.int32), budget=4)
    pool.release(0)
    pool.release(1)
    # both registered blocks cached (2 free): a stranger needing 3 blocks
    # must evict the LRU one (a's, released first) — b's keeps serving
    assert pool.can_admit(np.arange(30, 34, dtype=np.int32), budget=8)
    pool.acquire(0, np.arange(30, 34, dtype=np.int32), budget=8)
    assert pool._shared_prefix(b) != [] and pool._shared_prefix(a) == []
    # a row can never exceed blocks_per_row
    with pytest.raises(RuntimeError, match="table row"):
        pool.acquire(1, np.arange(1, 9, dtype=np.int32), budget=8)


def test_pool_reregistration_after_partial_prefix_eviction():
    # Evicting a SHALLOW prefix block while a deeper sibling stays cached
    # orphans the deep registration (the depth walk stops at the first
    # miss). A repeat of the prefix must supersede the orphan's registry
    # entry cleanly — the buggy overwrite left the orphan's _key_of alias
    # alive, so its eviction deleted the NEW block's registration and a
    # later eviction of the new block raised KeyError.
    pool = _pool(num_blocks=12, block_size=4, slots=4, blocks_per_row=4)
    prefix = np.arange(1, 9, dtype=np.int32)  # 8 tokens -> 2 full blocks
    pool.acquire(0, prefix, budget=4)  # 3 blocks; depths 0,1 register
    pool.release(0)  # both prefix blocks park cached, LRU front = depth 0
    # burn the 9 free blocks + force exactly ONE eviction (the shallow
    # depth-0 block) with prompts too short to register anything
    pool.acquire(1, np.array([100], np.int32), budget=11)  # 3 blocks
    pool.acquire(2, np.array([101], np.int32), budget=15)  # 4 blocks
    pool.acquire(3, np.array([102], np.int32), budget=11)  # 3, evicts one
    assert pool.stats()["blocks_cached"] == 1  # deep sibling survived
    pool.release(1)  # free capacity for the repeat
    # repeat of the same prefix: depth 0 misses, so fresh blocks register
    # both depths — the deep key collides with the orphaned cached block
    row, shared = pool.acquire(0, prefix, budget=4)
    assert shared == 0
    # invariant: registry and key_of are exact inverses, orphan freed
    assert pool.stats()["blocks_cached"] == 0
    assert {k: b for b, k in pool._key_of.items()} == {
        k: b for k, b in pool._registry.items()
    }
    # churn evictions through the re-registered blocks: must not KeyError,
    # and the prefix must still serve hits until its blocks are evicted
    pool.release(0)
    row2, shared2 = pool.acquire(0, prefix, budget=4)
    assert shared2 == 2 and (row2[:2] == row[:2]).all()
    pool.release(0)
    pool.release(2)
    pool.release(3)
    big = np.arange(50, 54, dtype=np.int32)
    pool.acquire(0, big, budget=12)        # 4 blocks
    pool.acquire(1, big + 100, budget=12)  # 4 blocks
    pool.acquire(2, big + 200, budget=8)   # 3: drains free, evicts both
    assert pool._shared_prefix(prefix) == []
    assert pool.active_blocks() == 11


# ------------------------------------------------------- the pool's runs
def _runs(blocks):
    """Lengths of the ascending runs ``blocks`` is made of, in row order."""
    breaks = np.flatnonzero(np.diff(blocks) != 1) + 1
    return np.diff(np.r_[0, breaks, len(blocks)]).tolist()


def test_pool_hands_a_fresh_request_its_blocks_as_two_runs():
    # the blocks a request registers (its full prompt blocks) from the top of
    # the pool, the rest from the bottom: each one ascending run
    pool = _pool(num_blocks=64, block_size=4, slots=3, blocks_per_row=16)
    row, _ = pool.acquire(0, np.arange(1, 11, dtype=np.int32), budget=14)  # 6 blocks, 2 full
    assert row[:6].tolist() == [62, 63, 1, 2, 3, 4]
    row, _ = pool.acquire(1, np.arange(20, 23, dtype=np.int32), budget=10)  # 4, none full
    assert row[:4].tolist() == [5, 6, 7, 8]
    row, _ = pool.acquire(2, np.arange(30, 42, dtype=np.int32), budget=4, defer_register=True)
    assert row[:4].tolist() == [59, 60, 61, 9]  # deferred: registered later, placed the same


def test_pool_finds_a_run_after_shuffled_releases_and_admits_without_one():
    rng = np.random.default_rng(0)
    pool = _pool(num_blocks=101, block_size=4, slots=8, blocks_per_row=25)
    for step in range(200):
        slot = int(rng.integers(8))
        pool.release(slot)
        # no full prompt block: every private block comes from the bottom
        budget = int(rng.integers(1, 80))
        need = pool.blocks_needed(1, budget)
        free = pool._is_free.copy()
        edges = np.flatnonzero(np.diff(free, prepend=False, append=False))
        longest = (edges[1::2] - edges[::2]).max() if edges.size else 0
        if not pool.can_admit(np.array([7], np.int32), budget):
            continue
        row, _ = pool.acquire(slot, np.array([7], np.int32), budget)
        blocks = row[:need]
        assert free[blocks].all() and len(set(blocks.tolist())) == need
        if longest >= need:  # a run of its length was free: it got one
            assert _runs(blocks) == [need]
        else:  # none was: admitted all the same, in runs to the walk's chunks at most
            assert sum(_runs(blocks)) == need


def test_pool_prefix_hits_lead_the_row_and_stay_while_active():
    pool = _pool(num_blocks=13, block_size=4, slots=3, blocks_per_row=8)
    prompt = np.arange(1, 13, dtype=np.int32)  # 3 full blocks
    row_a, _ = pool.acquire(0, prompt, budget=4)
    row_b, shared = pool.acquire(1, prompt, budget=8)
    assert shared == 3 and (row_b[:3] == row_a[:3]).all()
    pool.release(0)
    # a stranger that needs every block left: the shared blocks are active
    # (slot 1 holds them), so nothing of them is evicted
    stranger = np.arange(100, 101, dtype=np.int32)
    budget = 4 * pool.free_blocks() - 1
    assert pool.can_admit(stranger, budget) and not pool.can_admit(stranger, budget + 1)
    row_c, _ = pool.acquire(0, stranger, budget)
    assert not set(row_c[row_c > 0].tolist()) & set(row_b[:3].tolist())
    assert pool._shared_prefix(prompt) == row_b[:3].tolist()


def test_pool_evictions_go_through_the_spill_hook_lru_first():
    pool = _pool(num_blocks=9, block_size=4, slots=4, blocks_per_row=8)
    spilled = []
    pool.spill_fn = lambda key, blk: spilled.append(blk)
    first = [pool.acquire(s, np.arange(10 * s, 10 * s + 8, dtype=np.int32), budget=1)[0]
             for s in range(2)]  # 3 blocks each, 2 registered
    pool.release(1)
    pool.release(0)  # cached, LRU first: slot 1's two, then slot 0's
    row, _ = pool.acquire(2, np.array([99], np.int32), budget=27)  # 7 blocks: 4 free + 3 cached
    assert spilled == [first[1][0], first[1][1], first[0][0]]
    assert set(spilled) <= set(row[:7].tolist())


class _OneAtATime(PagedBlockPool):
    """The pool as it took blocks before runs: one at a time, a free block
    while any is left, else the LRU cached one, registering as it goes."""

    def acquire(self, slot, prompt, budget, defer_register=False):
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        assert self.can_admit(prompt, budget) and not defer_register
        bs, total = self.block_size, self.blocks_needed(len(prompt), budget)
        full = len(prompt) // bs
        hits = self._shared_prefix(prompt)
        row = []
        for blk in hits:
            if self._ref[blk] == 0:
                del self._cached[blk]
            self._ref[blk] += 1
            row.append(blk)
        for j in range(len(hits), total):
            free = np.flatnonzero(self._is_free)
            blk = int(free[-1]) if free.size else self._evict_one()
            self._is_free[blk] = False
            self._ref[blk] = 1
            if j < full:
                self._register(prompt[: (j + 1) * bs].tobytes(), blk)
            row.append(blk)
        self._rows[slot] = row
        self.tables[slot] = 0
        self.tables[slot, : len(row)] = row
        return self.tables[slot].copy(), len(hits)


def test_pool_admits_and_caches_as_a_pool_that_takes_blocks_one_at_a_time():
    # shared prefixes of six system prompts, cut at random depths, so that
    # hits, evictions of shallow blocks and orphaned deeper ones all happen
    # (this seed: 85 evictions, 5 orphans superseded and 77 blocks hit a pool)
    rng = np.random.default_rng(3)
    systems = [rng.integers(1, 50, size=24).astype(np.int32) for _ in range(6)]
    kw = dict(num_blocks=40, block_size=4, slots=5, blocks_per_row=12)
    pools = (PagedBlockPool(**kw, run_blocks=4), _OneAtATime(**kw))
    probes = [(s[: 4 * d + 2], 5) for s in systems for d in range(6)]
    for step in range(400):
        slot = int(rng.integers(5))
        if rng.random() < 0.4:
            for pool in pools:
                pool.release(slot)
        else:
            s = systems[int(rng.integers(len(systems)))]
            prompt = np.r_[s[: int(rng.integers(1, 24))], rng.integers(50, 60, size=2)]
            budget = int(rng.integers(1, 20))
            admit = [pool.can_admit(prompt, budget) and not pool._rows[slot] for pool in pools]
            assert admit[0] == admit[1]
            if admit[0]:
                shared = [pool.acquire(slot, prompt, budget)[1] for pool in pools]
                assert shared[0] == shared[1]
        runs, one = pools
        assert runs.free_blocks() == one.free_blocks(), step
        assert runs.stats()["blocks_cached"] == one.stats()["blocks_cached"], step
        assert sorted(runs._registry) == sorted(one._registry), step
        assert [runs.can_admit(*x) for x in probes] == [one.can_admit(*x) for x in probes], step


# ------------------------------------------------------------------ int8 KV
def test_kv_quantize_roundtrip_bound_and_determinism():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(scale=3.0, size=(2, 5, 8, 4, 16)).astype(np.float32))
    q, s = kv_quantize(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5, 8)
    deq = kv_dequantize(q, s, jnp.float32)
    # symmetric round-to-nearest: error <= scale/2 per element (+ulp slack)
    bound = np.asarray(s)[..., None, None] * 0.5 + 1e-6
    assert (np.abs(np.asarray(x - deq)) <= bound).all()
    q2, s2 = kv_quantize(x)
    assert np.array_equal(np.asarray(q), np.asarray(q2))
    assert np.array_equal(np.asarray(s), np.asarray(s2))


# -------------------------------------------------------- paged_attention op
def test_paged_attention_matches_dense_reference():
    rng = np.random.default_rng(1)
    b, h, kvh, d, bs, bpr = 2, 4, 2, 8, 4, 3
    S = bs * bpr
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, S, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, S, kvh, d)).astype(np.float32))
    k_pool = k.reshape(b * bpr, bs, kvh, d)
    v_pool = v.reshape(b * bpr, bs, kvh, d)
    tables = jnp.arange(b * bpr, dtype=jnp.int32).reshape(b, bpr)
    pos = jnp.asarray([5, 9], jnp.int32)
    out = np.asarray(paged_attention(q, k_pool, v_pool, tables, pos))
    for i, p in enumerate((5, 9)):
        ref = np.asarray(dot_product_attention(
            q[i : i + 1], k[i : i + 1, : p + 1], v[i : i + 1, : p + 1],
            causal=False,
        ))[0]
        # Guarded: the paged op is the dense op on the gathered rows — same
        # scores, same softmax, masked keys weighing exactly 0. It sums 12
        # terms (the last ones zeros) where the reference sums p + 1, and
        # XLA's CPU backend vectorises the two reduction lengths in another
        # order, which costs the last bit: equal to 4 float32 ulps of the
        # largest output. A wrong row, block or mask is off by tenths.
        np.testing.assert_allclose(
            out[i], ref, rtol=0,
            atol=4 * np.finfo(np.float32).eps * np.abs(ref).max(),
        )
    # int8 pools: dequantization inside the op, bounded divergence
    qk, sk = kv_quantize(k_pool)
    qv, sv = kv_quantize(v_pool)
    out8 = np.asarray(
        paged_attention(q, qk, qv, tables, pos, k_scale=sk, v_scale=sv)
    )
    assert np.abs(out8 - out).max() < 0.1


# ----------------------------------------------------- engine bitwise parity
def test_engine_dense_vs_paged_bitwise_parity_with_block_recycling(model, get_engine):
    """Three waves through a 2-slot paged engine whose pool is deliberately
    tiny (9 blocks vs the 17 a full provision would take): wave 2+ decodes
    into blocks recycled from earlier occupants, and every wave must still
    match the dense static reference bitwise."""
    eng = get_engine(slots=2, max_len=32, pool_blocks=9)
    assert eng.stats()["kv"]["backend"] == "paged"
    budgets = [5, 7]
    for s in (1, 2, 3):
        wave = _prompts(2, seed=s)
        occs = [
            eng.insert(p, max_new_tokens=b, pad_token_id=0, tag=i)
            for i, (p, b) in enumerate(zip(wave, budgets))
        ]
        retired = eng.drain()
        assert sorted(o.tag for o in retired) == [0, 1]
        for p, b, occ in zip(wave, budgets, occs):
            np.testing.assert_array_equal(occ.output_row(), _ref(model, p, b))
    stats = eng.stats()
    assert stats["programs"] == {"prefill_insert": 1, "decode_step": 1}
    kv = stats["kv"]
    assert kv["blocks_active"] == 0 and kv["reserved_tokens"] == 0


def test_engine_prefix_cache_dedups_shared_system_prompt(model, get_engine):
    """Same block-aligned system prompt on every request: after the first,
    each admission hits the registry for all full prefix blocks — across
    live sharers AND across waves via the cached tier."""
    eng = get_engine(slots=2, max_len=64, block_size=8)
    sys_prompt = _prompts(1, lens=(8,), seed=7)[0]  # one full shared block
    for wave in range(2):
        occs = [
            eng.insert(sys_prompt + [50 + wave, i], max_new_tokens=4,
                       pad_token_id=0, tag=i)
            for i in range(2)
        ]
        eng.drain()
        for i, occ in enumerate(occs):
            np.testing.assert_array_equal(
                occ.output_row(), _ref(model, sys_prompt + [50 + wave, i], 4)
            )
    kv = eng.stats()["kv"]
    # 4 requests sharing one full prompt block: only the first allocates it
    # (the second wave hits through the cached tier, across retirement)
    assert kv["prefix_hits"] == 3 and kv["prefix_misses"] == 1
    assert kv["prefix_hit_rate"] == pytest.approx(0.75)


def test_engine_int8_kv_deterministic_and_close_to_dense(model, get_engine):
    eng = get_engine(slots=2, max_len=32, kv_cache="paged_int8", pool_blocks=9)
    assert eng.stats()["kv"]["backend"] == "paged_int8"
    prompts = _prompts(2, seed=11)
    budgets = [6, 8]
    runs = []
    for _ in range(2):
        eng.reset()
        occs = [
            eng.insert(p, max_new_tokens=b, pad_token_id=0)
            for p, b in zip(prompts, budgets)
        ]
        eng.drain()
        runs.append([occ.output_row() for occ in occs])
    agree = total = 0
    for p, b, r0, r1 in zip(prompts, budgets, runs[0], runs[1]):
        np.testing.assert_array_equal(r0, r1)  # bitwise deterministic
        np.testing.assert_array_equal(r0[: len(p)], p)  # prompt echo intact
        dense = _ref(model, p, b)
        agree += int((r0[len(p):] == dense[len(p):]).sum())
        total += b
    # bounded divergence: quantization error may flip some greedy argmaxes
    # but most generated tokens must agree with the dense reference
    assert agree / total >= 0.5


# ------------------------------------------------------------- admission gate
def test_backend_validate_request_names_paged_knobs(model):
    backend = make_kv_backend(
        "paged", config=model.config, slots=2, max_len=64, prompt_bucket=16,
        block_size=8, pool_blocks=4,
    )
    with pytest.raises(ValueError, match=r"engine_block_size=8"):
        backend.validate_request(prompt_len=4, budget=30)
    with pytest.raises(ValueError, match=r"engine_pool_blocks"):
        backend.validate_request(prompt_len=4, budget=30)
    backend.validate_request(prompt_len=4, budget=10)  # 2 blocks: fits


def test_engine_can_admit_gates_on_free_blocks(model, get_engine):
    eng = get_engine(slots=2, max_len=32, pool_blocks=9)  # 8 allocatable
    p = _prompts(2, lens=(9, 12), seed=13)
    a = eng.insert(p[0], max_new_tokens=15, pad_token_id=0)  # 3 blocks
    eng.insert(p[1], max_new_tokens=12, pad_token_id=0)  # 3 blocks
    # both slots busy -> no slot either way; free the accounting question by
    # asking the backend directly: 2 free blocks < 3 needed
    assert not eng._backend.can_admit(np.arange(1, 10, dtype=np.int32), 15)
    assert eng._backend.can_admit(np.arange(1, 10, dtype=np.int32), 5)
    eng.drain()
    assert eng.can_admit(np.arange(1, 10, dtype=np.int32), 15)
    assert a.finished


def test_serving_config_validates_paged_knobs():
    with pytest.raises(ValueError, match="kv_cache"):
        ServingConfig(kv_cache="paged_int4")
    with pytest.raises(ValueError, match="engine_block_size"):
        ServingConfig(mode="continuous", kv_cache="paged",
                      engine_max_len=60, engine_block_size=16)
    with pytest.raises(ValueError, match="engine_pool_blocks"):
        ServingConfig(kv_cache="paged", engine_pool_blocks=1)
    ServingConfig(mode="continuous", kv_cache="paged", engine_max_len=64,
                  engine_block_size=16)  # valid


# ------------------------------------------------------------ server parity
def test_server_paged_parity_and_kv_gauges(model, get_engine):
    eng = get_engine(slots=2, max_len=64, block_size=8)
    cfg = ServingConfig(
        mode="continuous", engine_slots=2, engine_max_len=64,
        engine_prompt_bucket=16, engine_readback_lag=2,
        kv_cache="paged", engine_block_size=8,
    )
    shared = _prompts(1, lens=(8,), seed=17)[0]  # one full shared block
    prompts = [shared + [i] for i in range(4)]
    budgets = [6, 4, 8, 5]
    with InferenceServer(model, cfg, engine=eng) as srv:
        futs = [
            srv.submit(p, max_new_tokens=b, pad_token_id=0)
            for p, b in zip(prompts, budgets)
        ]
        cont = [f.result(timeout=120) for f in futs]
        snap = srv.metrics.snapshot()
    for p, b, res in zip(prompts, budgets, cont):
        np.testing.assert_array_equal(res.tokens, _ref(model, p, b))
    assert snap["serving/kv_hbm_bytes"] == eng.stats()["kv"]["hbm_bytes"] > 0
    assert snap["serving/prefix_hit_rate"] == pytest.approx(0.75)  # 3 of 4
    assert 0.0 <= snap["serving/kv_utilization"] <= 1.0


def test_server_static_mode_routes_kv_backend_to_generate(model):
    cfg = ServingConfig(
        mode="static", kv_cache="paged", engine_block_size=8,
        max_batch_size=1, batch_window_s=0.0, batch_bucket=False,
    )
    p = _prompts(1, seed=19)[0]
    with InferenceServer(model, cfg) as srv:
        res = srv.submit(p, max_new_tokens=6, pad_token_id=0).result(timeout=120)
    np.testing.assert_array_equal(res.tokens, _ref(model, p, 6))


# ----------------------------------------------------------- memory economics
def test_paged_pool_hbm_is_smaller_and_stats_track_live_tokens(model, get_engine):
    dense = make_kv_backend("dense", config=model.config, slots=8,
                            max_len=256, prompt_bucket=16)
    paged = make_kv_backend("paged", config=model.config, slots=8,
                            max_len=256, prompt_bucket=16, block_size=16,
                            pool_blocks=33)  # 4x oversubscribed
    int8 = make_kv_backend("paged_int8", config=model.config, slots=8,
                           max_len=256, prompt_bucket=16, block_size=16,
                           pool_blocks=33)
    assert paged.hbm_bytes() < dense.hbm_bytes() / 3
    assert int8.hbm_bytes() < paged.hbm_bytes()
    # live-vs-reserved utilization from a real engine
    eng = get_engine(slots=2, max_len=64, block_size=8)
    occ = eng.insert(_prompts(1, seed=23)[0], max_new_tokens=6, pad_token_id=0)
    kv = eng.stats()["kv"]
    assert kv["reserved_tokens"] > 0
    assert 0.0 < kv["utilization"] <= 1.0
    assert eng.live_tokens() == len(occ.prompt) + len(occ.tokens)
    eng.drain()
    assert eng.stats()["kv"]["utilization"] == 0.0
    assert eng.peak_live == 1


@pytest.mark.parametrize(
    "family,kv_cache,attention_impl",
    [("llama", "paged", "pallas"), ("llama", "paged", "reference"), ("llama", "dense", "reference"),
     ("axk1", "paged", "pallas")],
)
def test_walked_tokens_count_whole_chunks_of_the_decode_kernel(model, family, kv_cache, attention_impl):
    """`kv_walked_tokens` of the `engine.decode_step` span: each slot's live
    positions rounded up to the decode kernel's chunk (the kernel's own
    function, whose chunk is four times as long over a latent pool: A.X-K1's),
    a chunk for the vacant slot; nothing where that kernel does not run: the
    reference attention over a pool, a cache without one."""
    from accelerate_tpu.models.axk1 import AxK1Config, create_axk1
    from accelerate_tpu.ops.paged_decode import decode_chunk_positions, decode_walked_positions

    latent = family == "axk1"
    if latent:
        model = create_axk1(AxK1Config.tiny(compute_dtype=jnp.float32), seed=3)
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prompt_bucket=16, kv_cache=kv_cache,
        block_size=8, attention_impl=attention_impl,
    )
    chunk = decode_chunk_positions(8, latent) if attention_impl == "pallas" else 0
    assert eng.walked_tokens() == 2 * chunk  # both vacant
    occ = eng.insert(_prompts(1, seed=31)[0], max_new_tokens=6, pad_token_id=0)
    live = len(occ.prompt) + len(occ.tokens)
    assert eng.live_tokens() == live
    if attention_impl == "pallas":
        assert eng.walked_tokens() == decode_walked_positions(live, 8, latent) + chunk
    else:
        assert eng.walked_tokens() == 0
    eng.drain()
    assert eng.walked_tokens() == 2 * chunk


@pytest.mark.parametrize(
    "family,attention_impl", [("llama", "pallas"), ("llama", "reference"), ("axk1", "pallas")])
def test_copy_counters_of_the_decode_step_follow_the_kernels_rule(
        model, monkeypatch, family, attention_impl):
    """`kv_live_blocks` and `kv_copy_descriptors` of the `engine.decode_step`
    span: each slot's live blocks and the copies the decode kernel issues for
    them by `decode_copy_descriptors` over the slot's table row (the rule the
    kernel's own copies are held to in tests/test_paged_decode_runs.py: runs
    over a latent pool, a copy a block over a per-head one), one block and one
    copy for a vacant slot; nothing where the kernel does not run."""
    from accelerate_tpu import tracing
    from accelerate_tpu.models.axk1 import AxK1Config, create_axk1
    from accelerate_tpu.ops.paged_decode import decode_copy_descriptors

    latent = family == "axk1"
    if latent:
        model = create_axk1(AxK1Config.tiny(compute_dtype=jnp.float32), seed=3)
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prompt_bucket=32, kv_cache="paged",
        block_size=8, attention_impl=attention_impl,
    )
    kernel = attention_impl == "pallas"
    assert eng.walk_copies() == ((2, 2) if kernel else (0, 0))  # both vacant
    # 30 positions: 3 registered prompt blocks (one run from the top of a
    # fresh pool) and a fourth from the bottom, so over a latent pool 2 + 1
    # copies for 4 blocks; a per-head pool takes a copy a block
    occ = eng.insert(_prompts(1, lens=(30,), seed=31)[0], max_new_tokens=20, pad_token_id=0)
    row = eng._backend.pool.tables[occ.slot]
    copies = 3 if latent else 4
    assert decode_copy_descriptors(row, 30, 8, latent) == copies
    assert eng.walk_copies() == ((5, copies + 1) if kernel else (0, 0))
    seen = []
    real = tracing.span
    monkeypatch.setattr(tracing, "span", lambda name, **attrs: (
        seen.append(attrs) if name == "engine.decode_step" else None, real(name, **attrs))[1])
    for _ in range(3):
        eng.step()
    for attrs in seen:  # each step's pair is the rule's for what the step held
        live = attrs["kv_live_tokens"]
        want = (-(-live // 8) + 1, decode_copy_descriptors(row, live, 8, latent) + 1)
        assert (attrs["kv_live_blocks"], attrs["kv_copy_descriptors"]) == (
            want if kernel else (0, 0))
    assert len(seen) == 3
    eng.drain()
    assert eng.walk_copies() == ((2, 2) if kernel else (0, 0))


def test_device_tables_are_a_copy_of_the_hosts_rows(model):
    """The tables a step is dispatched with stay as they were when the next
    admission writes its row. On the CPU client `jnp.asarray` takes a numpy
    array that lies on a 64-byte boundary without copying it (one process in
    a few gets such tables from the allocator), and a step still in flight
    then read the new row: a vacant slot scattered its keys into the shared
    prefix blocks of the request beside it, which decoded other tokens
    (`test_lfm2.py::test_a_prompt_sent_twice...`, 4 of 8 such trials; PR 31)."""
    backend = make_kv_backend(
        "paged", config=model.config, slots=4, max_len=48, prompt_bucket=16, block_size=4,
    )
    tables = backend.pool.tables
    raw = np.zeros(tables.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    backend.pool.tables = (
        raw[start:start + tables.nbytes].view(tables.dtype).reshape(tables.shape)
    )
    prompt = _prompts(1, lens=(11,), seed=5)[0]
    backend.acquire(0, prompt, 8)
    dispatched = backend.device_tables()
    seen = np.array(dispatched)
    backend.acquire(1, prompt, 8)  # shares the first's two whole blocks
    np.testing.assert_array_equal(np.asarray(dispatched), seen)
    assert np.asarray(backend.device_tables())[1, 0] == seen[0, 0] != seen[1, 0]


# --------------------------------------------------------- static generate()
def test_generate_paged_backends_match_dense(model):
    rng = np.random.default_rng(29)
    ids = rng.integers(1, 255, size=(2, 9)).astype(np.int32)
    dense = np.asarray(generate(model, ids, max_new_tokens=10))
    paged = np.asarray(
        generate(model, ids, max_new_tokens=10, kv_backend="paged",
                 kv_block_size=8)
    )
    np.testing.assert_array_equal(dense, paged)
    int8_a = np.asarray(
        generate(model, ids, max_new_tokens=10, kv_backend="paged_int8",
                 kv_block_size=8)
    )
    int8_b = np.asarray(
        generate(model, ids, max_new_tokens=10, kv_backend="paged_int8",
                 kv_block_size=8)
    )
    np.testing.assert_array_equal(int8_a, int8_b)
    np.testing.assert_array_equal(int8_a[:, :9], ids)
    with pytest.raises(ValueError, match="kv_backend"):
        generate(model, ids, max_new_tokens=4, kv_backend="dense8")


# ------------------------------------- the pool goes through a program whole
def _tiny_pallas_engine(family):
    from accelerate_tpu.models.gpt2 import GPT2Config, create_gpt2

    model = (create_gpt2(GPT2Config.tiny(), seed=0) if family == "gpt2"
             else create_llama(LlamaConfig.tiny(), seed=0))

    def engine(attention_impl):
        return ContinuousBatchingEngine(
            model, slots=2, max_len=32, prompt_bucket=16, kv_cache="paged",
            block_size=8, attention_impl=attention_impl,
        )

    return model, engine


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_decode_program_never_slices_the_pool_by_layer(family):
    """The structure PR 28 bought (kvcache.py, "The pool's layout"): in the
    engine's decode program the layer loop carries the pool whole. No
    operation cuts one layer's slice out of it, writes one back, transposes
    or copies one, and the donated pool is the result's own buffer. On the
    chip each of these was a copy that scaled with the pool, every layer of
    every step."""
    import re

    model, engine = _tiny_pallas_engine(family)
    eng = engine("pallas")
    pool = eng._donated["cache"]["k"]
    layers, blocks, block_size = pool.shape[:3]
    assert pool.ndim == 4 and layers == model.config.num_hidden_layers
    lowered = eng._decode_jit.lower(
        eng._donated, eng._carried, model.params, eng._backend.device_tables()
    )
    # StableHLO: ops of the traced program whose result is a layer's slice
    slice_type = rf"-> tensor<(1x)?{blocks}x{block_size}x[0-9x]*(bf16|f32|i8)>"
    cuts = [
        line.strip() for line in lowered.as_text().splitlines()
        if re.search(r"dynamic_slice|dynamic_update_slice|transpose", line)
        and re.search(slice_type, line)
    ]
    assert not cuts, cuts[:3]
    # the compiled program: nothing at all of a layer slice's shape (the
    # compiler's own copies included), and the pool aliased to the result
    compiled = lowered.compile().as_text()
    slice_shape = rf"= \w+\[(1,)?{blocks},{block_size},[0-9,]*\]"
    made = [line.strip() for line in compiled.splitlines() if re.search(slice_shape, line)]
    assert not made, made[:3]
    header = compiled.split("\n", 1)[0]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", header).group(1)
    # parameters 0 and 1 are the donated K and V pools (the first leaves of
    # the first argument); each is some output's buffer
    assert re.search(r"\(0, \{\}, may-alias\)", aliases), aliases
    assert re.search(r"\(1, \{\}, may-alias\)", aliases), aliases


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_engine_pallas_vs_reference_paged_bitwise_on_a_fixed_script(family):
    """The same requests in the same order through the paged engine with the
    Pallas kernels and with the reference op: greedy and sampled rows, two
    waves so that the second decodes into recycled blocks. Token for token
    the same."""
    _, engine = _tiny_pallas_engine(family)

    def run(eng):
        out = []
        for wave in (1, 2):
            prompts = _prompts(2, seed=wave)
            occs = [
                eng.insert(prompts[0], max_new_tokens=6, pad_token_id=0, tag=0),
                eng.insert(prompts[1], max_new_tokens=9, pad_token_id=0, tag=1,
                           temperature=0.8, top_k=5, top_p=0.9, seed=wave),
            ]
            eng.drain()
            out += [occ.output_row().tolist() for occ in occs]
        assert eng.stats()["programs"] == {"prefill_insert": 1, "decode_step": 1}
        return out

    pallas, reference = run(engine("pallas")), run(engine("reference"))
    assert pallas == reference


def test_alternating_sliding_window_paged_matches_dense_bitwise():
    """Gemma-2-style alternating local/global layers fall back to the
    reference paged path, whose layer loop carries the pool whole with the
    per-layer ``sliding`` flag beside the layer index, in the decode and the
    verify program: tokens equal the dense arena's."""
    cfg = LlamaConfig.tiny(
        compute_dtype=jnp.float32, sliding_window=8, alternating_sliding_window=True
    )
    model = create_llama(cfg, seed=0)
    prompts = _prompts(2, lens=(5, 12), seed=0)
    prompts[1] = prompts[1][:4] * 3  # repetitive: the n-gram drafter fires
    outs = {}
    for kv_cache in ("dense", "paged"):
        eng = ContinuousBatchingEngine(
            model, slots=2, max_len=32, prompt_bucket=16, kv_cache=kv_cache,
            block_size=8, spec="ngram",
        )
        occs = [eng.insert(p, max_new_tokens=12, pad_token_id=0) for p in prompts]
        eng.drain()
        assert eng.stats()["programs"]["verify_step"] == 1
        outs[kv_cache] = [occ.output_row().tolist() for occ in occs]
    assert outs["paged"] == outs["dense"]


# ------------------------------------------------------ two kinds of state
def _lfm2_backends(slots=4, max_len=32):
    from accelerate_tpu.models.lfm2 import Lfm2Config

    config = Lfm2Config.tiny()  # 2 attention and 3 conv layers of 5, bf16 compute
    kw = dict(config=config, slots=slots, max_len=max_len, prompt_bucket=16, block_size=8)
    return config, {kind: make_kv_backend(kind, **kw) for kind in ("dense", "paged", "paged_int8")}


@pytest.mark.parametrize("kind", ["dense", "paged", "paged_int8"])
def test_backend_allocates_both_kinds_of_state(kind):
    """The family says what a slot keeps: keys and values over its attention
    layers only (not the model's depth), and a recurrent row a slot over its
    convolution layers, counted beside the keys and values, not among them."""
    config, backends = _lfm2_backends()
    backend = backends[kind]
    state = backend.init_device_state()
    assert set(state) == {"k", "v", "recurrent"}
    assert state["recurrent"].shape == (3, 4, 2, config.hidden_size)
    assert state["recurrent"].dtype == jnp.bfloat16
    keys = state["k"]["q"] if kind == "paged_int8" else state["k"]
    assert keys.shape[0] == config.attention_layers == 2
    recurrent = 3 * 4 * 2 * config.hidden_size * 2
    assert backend.recurrent_state_bytes() == recurrent == backend.stats()["recurrent_state_bytes"]
    per_position = 2 * config.num_key_value_heads * config.head_dim  # two layers' heads
    if kind == "dense":
        assert backend.hbm_bytes() == 2 * 4 * 32 * per_position * 2
    elif kind == "paged":
        assert backend.hbm_bytes() == 2 * (4 * 4 + 1) * 8 * per_position * 2
    # a family whose only state is keys and values has no third leaf and no bytes
    plain = make_kv_backend(kind, config=LlamaConfig.tiny(), slots=4, max_len=32,
                            prompt_bucket=16, block_size=8)
    assert set(plain.init_device_state()) == {"k", "v"}
    assert plain.recurrent_state_bytes() == 0 == plain.stats()["recurrent_state_bytes"]


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_prefill_write_stores_the_recurrent_row_and_release_clears_nothing(kind):
    """``prefill_write`` replaces the slot's recurrent row and no other;
    ``release`` leaves it (the next prefill overwrites it), and a released slot
    taken again holds the new occupant's state."""
    config, backends = _lfm2_backends()
    backend = backends[kind]
    cache = backend.init_device_state()

    def prefilled(value):
        heads = (config.num_key_value_heads, config.head_dim)
        return {
            "k": jnp.full((2, 1, 32, *heads), value, jnp.bfloat16),
            "v": jnp.full((2, 1, 32, *heads), value, jnp.bfloat16),
            "recurrent": jnp.full((3, 1, 2, config.hidden_size), value, jnp.bfloat16),
        }

    row, _ = backend.acquire(2, np.arange(10, dtype=np.int32), 6)
    cache = backend.prefill_write(cache, prefilled(1.0), jnp.int32(2), jnp.asarray(row), 16)
    assert float(cache["recurrent"][:, 2].min()) == 1.0
    assert float(jnp.abs(cache["recurrent"][:, jnp.array([0, 1, 3])]).max()) == 0.0
    backend.release(2)
    row, _ = backend.acquire(2, np.arange(5, dtype=np.int32) + 50, 4)
    cache = backend.prefill_write(cache, prefilled(3.0), jnp.int32(2), jnp.asarray(row), 16)
    assert float(cache["recurrent"][:, 2].min()) == float(cache["recurrent"][:, 2].max()) == 3.0
    if kind == "paged":
        assert backend.reserved_tokens() == 16  # 9 positions in blocks of 8: keys and values only


def test_engine_stats_count_keys_and_values_and_report_recurrent_bytes():
    from accelerate_tpu.models.lfm2 import Lfm2Config, create_lfm2

    model = create_lfm2(Lfm2Config.tiny(), seed=0)
    eng = ContinuousBatchingEngine(model, slots=2, max_len=32, prompt_bucket=16,
                                   kv_cache="paged", block_size=8)
    eng.insert(list(range(1, 12)), max_new_tokens=4, pad_token_id=0)
    stats = eng.stats()
    assert stats["recurrent_state_bytes"] == 3 * 2 * 2 * 64 * 2
    assert stats["kv"]["hbm_bytes"] == 2 * (2 * 4 + 1) * 8 * (2 * 2 * 16) * 2
    assert stats["kv"]["live_tokens"] == 11 and stats["kv"]["reserved_tokens"] == 16
    eng.drain()
    released = eng.stats()
    assert released["kv"]["live_tokens"] == 0 and released["recurrent_state_bytes"] == 3 * 2 * 2 * 64 * 2


# --------------------------------------------- one seam between step and cache
def test_model_families_reach_the_cache_only_through_the_seam():
    """The layering (kvcache.py, "the seam to a model's step"): what the store
    is and which attend path runs is decided in kvcache.py, so no module under
    ``models/`` imports the paged kernels or asks whether a pool is the int8
    ``{"q","s"}`` pair, and ``gpt2.py`` and ``lfm2.py`` take from their sibling
    ``llama.py`` model mathematics by its public names only: of its private
    names the loss's helper and the sequence body's remat policy, nothing
    cache-facing."""
    import ast
    import pathlib

    import accelerate_tpu.models as models

    private_from_llama = {"_ce_from_hidden", "_remat_policy"}
    for path in sorted(pathlib.Path(models.__file__).parent.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names] + [getattr(node, "module", None) or ""]
                assert not any("paged_decode" in n for n in names), (path.name, node.lineno)
                if (isinstance(node, ast.ImportFrom) and node.module == "llama"
                        and path.name in ("gpt2.py", "lfm2.py")):
                    private = {a.name for a in node.names if a.name.startswith("_")}
                    assert private <= private_from_llama, (path.name, private)
            is_dict_test = (
                isinstance(node, ast.Call) and getattr(node.func, "id", None) == "isinstance"
                and getattr(node.args[1], "id", None) == "dict"
            )
            if is_dict_test:  # the loss's ``isinstance(out, dict)`` is the one
                assert (path.name, node.args[0].id) == ("llama.py", "out"), (path.name, node.lineno)
