"""A prompt's prefill is padded to the smallest rung that holds it (engine.py
``prefill_rungs``): the bucket, and half of it where that half is at least 128
positions of whole blocks. Over the five serving families' tiny models, in float32,
on an engine with a bucket of 256 in 512 positions and blocks of 16:

* prompts of 1, 128, 129 and 256 tokens take the rungs 128, 128, 256 and 256, one
  compiled prefill a rung, counted by ``stats()["prefill_rungs"]`` and named by the
  ``bucket`` of the ``engine.prefill`` span;
* a prompt on the half rung gives the greedy tokens it gives padded to the whole
  bucket, and first-token logits within 1e-5;
* a half-rung prefill writes its rung's blocks of a paged pool and leaves the
  slot's later blocks, and a shared (copy-on-write) prefix, as they were.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import tracing
from accelerate_tpu.engine import ContinuousBatchingEngine, prefill_rungs

FAMILIES = ["gpt2", "llama", "lfm2", "axk1", "longcat"]
BUCKET, MAX_LEN, BLOCK = 256, 512, 16


def _model(family):
    f32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    if family == "gpt2":
        from accelerate_tpu.models.gpt2 import GPT2Config, create_gpt2

        return create_gpt2(GPT2Config.tiny(max_position_embeddings=MAX_LEN, **f32), seed=0)
    if family == "llama":
        from accelerate_tpu.models.llama import LlamaConfig, create_llama

        return create_llama(LlamaConfig.tiny(**f32), seed=0)
    if family == "lfm2":
        from accelerate_tpu.models.lfm2 import Lfm2Config, create_lfm2

        return create_lfm2(Lfm2Config.tiny(**f32), seed=3)
    if family == "axk1":
        from accelerate_tpu.models.axk1 import AxK1Config, create_axk1

        return create_axk1(AxK1Config.tiny(**f32), seed=3)
    from accelerate_tpu.models.longcat import LongcatFlashConfig, create_longcat

    return create_longcat(LongcatFlashConfig.tiny(**f32), seed=3)


_ENGINES: dict = {}


def _engine(family, kv_cache="paged"):
    """One engine a family and store, shared by the tests (reset on handout)."""
    key = (family, kv_cache)
    if key not in _ENGINES:
        _ENGINES[key] = ContinuousBatchingEngine(
            _model(family), slots=2, max_len=MAX_LEN, prompt_bucket=BUCKET,
            kv_cache=kv_cache, block_size=BLOCK, readback_lag=0,
        )
    eng = _ENGINES[key]
    eng.reset()
    return eng


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=n).astype(np.int32)


def test_the_rungs_follow_from_the_bucket_and_the_block():
    assert prefill_rungs(256, 16) == (128, 256)
    assert prefill_rungs(1024, 16) == (512, 1024)
    assert prefill_rungs(512, 16) == (256, 512)
    # under 128 positions, or not whole blocks: the bucket alone
    for bucket, block in [(8, 4), (16, 16), (255, 16), (254, 16), (256, 96)]:
        assert prefill_rungs(bucket, block) == (bucket,)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_prompt_takes_the_smallest_rung_that_holds_it(family):
    eng = _engine(family)
    assert eng.rungs == (128, 256)
    before = eng.stats()["prefill_rungs"]
    lengths = (1, 128, 129, 256)
    assert [eng._rung(n) for n in lengths] == [128, 128, 256, 256]
    tag = types.SimpleNamespace(trace_id=f"rungs-{family}")
    for n in lengths:
        eng.insert(_prompt(n), max_new_tokens=2, pad_token_id=0, tag=tag)
        eng.drain()
    stats = eng.stats()
    assert stats["programs"]["prefill_insert"] == 2
    assert {r: stats["prefill_rungs"][r] - before[r] for r in eng.rungs} == {128: 2, 256: 2}
    spans = tracing.get_tracer().spans(trace_id=tag.trace_id, name="engine.prefill")
    assert [(s.attrs["prompt_len"], s.attrs["bucket"]) for s in spans] == [
        (1, 128), (128, 128), (129, 256), (256, 256)]


@pytest.mark.parametrize("family", FAMILIES)
def test_the_half_rung_gives_what_the_full_bucket_gives(family, monkeypatch):
    eng = _engine(family)
    prompt = _prompt(100, seed=1)

    def tokens():
        occ = eng.insert(prompt, max_new_tokens=6, pad_token_id=0)
        eng.drain()
        eng.reset()
        return occ.output_row()

    half = tokens()
    monkeypatch.setattr(eng, "_rung", lambda n: BUCKET)
    full = tokens()
    np.testing.assert_array_equal(half, full)

    family_ = eng.config.serving_family()
    last = jnp.asarray([len(prompt) - 1])

    def first_logits(width):
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(prompt)] = prompt
        logits, *_ = family_.prefill_at(eng.config, eng.model.params, jnp.asarray(padded),
                                        MAX_LEN, last)
        return np.asarray(logits)

    np.testing.assert_allclose(first_logits(128), first_logits(BUCKET), rtol=0, atol=1e-5)


def _fill(eng, value):
    """Every block of the pool holds ``value``, so a block no program wrote is seen."""
    def fill(leaf):
        return jnp.full_like(leaf, value)

    eng._donated = {**eng._donated, "cache": jax.tree.map(fill, eng._donated["cache"])}


def _blocks(eng, ids):
    """The pool's keys at ``ids``, one array a block (the int8 pool's codes)."""
    keys = eng._donated["cache"]["k"]
    keys = keys["q"] if isinstance(keys, dict) else keys
    return [np.asarray(keys[:, int(b)]) for b in ids]


@pytest.mark.parametrize("kv_cache", ["paged", "paged_int8"])
def test_a_half_rung_prefill_writes_its_rungs_blocks_only(kv_cache):
    eng = _engine("llama", kv_cache)
    _fill(eng, 7)
    occ = eng.insert(_prompt(100), max_new_tokens=200, pad_token_id=0)
    row = eng._backend.pool.tables[occ.slot]
    held = -(-(100 + 200) // BLOCK)
    assert (row[:held] != 0).all() and (row[held:] == 0).all()
    written = -(-128 // BLOCK)  # ceil(rung / block_size) = 8 of the 19 held
    blocks = _blocks(eng, row[:held])
    assert all((b != 7).any() for b in blocks[:written])
    assert all((b == 7).all() for b in blocks[written:])
    eng.drain()


def test_a_half_rung_sharer_leaves_the_shared_prefix_as_it_was():
    """A's prompt (200 tokens, the whole bucket) registers 12 full blocks; B's
    (120 tokens, the half rung) shares the first 6. A decodes beside B what it
    decodes alone; B's prefill writes neither the shared blocks (marked after
    A's prefill, as another rung's rounding would leave them) nor its own
    blocks past its rung."""
    eng = _engine("llama")
    a = _prompt(200, seed=2)
    b = np.concatenate([a[:96], _prompt(24, seed=3)])

    def admit_both(mark=None):
        eng.reset()
        _fill(eng, 7)
        occ_a = eng.insert(a, max_new_tokens=8, pad_token_id=0)
        row_a = eng._backend.pool.tables[occ_a.slot].copy()
        if mark is not None:
            cache = eng._donated["cache"]
            ids = jnp.asarray(row_a[:12])
            eng._donated = {**eng._donated, "cache": {
                **cache, "k": cache["k"].at[:, ids].set(mark)}}
        hits = eng.stats()["kv"]["prefix_hits"]
        occ_b = eng.insert(b, max_new_tokens=40, pad_token_id=0)
        assert eng.stats()["kv"]["prefix_hits"] - hits == 6
        return occ_a, row_a, occ_b

    occ = eng.insert(a, max_new_tokens=8, pad_token_id=0)
    eng.drain()
    alone = occ.output_row()

    occ_a, _, _ = admit_both()
    eng.drain()
    np.testing.assert_array_equal(occ_a.output_row(), alone)

    _, row_a, occ_b = admit_both(mark=5)
    row_b = eng._backend.pool.tables[occ_b.slot]
    np.testing.assert_array_equal(row_b[:6], row_a[:6])
    assert all((blk == 5).all() for blk in _blocks(eng, row_a[:12]))
    held = -(-(120 + 40) // BLOCK)
    own = _blocks(eng, row_b[6:held])
    assert all((blk != 7).any() for blk in own[:2])  # blocks 6 and 7: the rung's last
    assert all((blk == 7).all() for blk in own[2:])
    eng.drain()
