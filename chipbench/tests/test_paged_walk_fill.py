"""``paged_walk_fill.serve`` on hand-made session spans: the mean of live over
walked, and nothing where the program's spans lack the counter (the parent of the
PR that brought it, a dense cache) or there is no session."""

import types

import pytest

from chipbench import hostspans, lib


def decode_step(**attrs):
    return types.SimpleNamespace(name="engine.decode_step", t0=0.0, t1=0.001, id=1, parent=0,
                                 attrs=dict(decoding=4, slots=4, **attrs))


WITH = [decode_step(kv_live_tokens=600, kv_reserved_tokens=1000, kv_walked_tokens=768),
        decode_step(kv_live_tokens=640, kv_reserved_tokens=1000, kv_walked_tokens=1024)]
WITHOUT = [decode_step(kv_live_tokens=600, kv_reserved_tokens=1000)]
DENSE = [decode_step(kv_live_tokens=600, kv_reserved_tokens=1000, kv_walked_tokens=0)]


@pytest.mark.parametrize("spans,expected", [
    (WITH, 100 * (600 / 768 + 640 / 1024) / 2), (WITHOUT, None), (DENSE, None), ([], None), (None, None),
], ids=["counted", "parent_has_no_counter", "no_block_pool", "empty_session", "no_session"])
def test_reader(monkeypatch, spans, expected):
    monkeypatch.setattr(
        hostspans, "session_spans",
        lambda name=None: None if spans is None else [sp for sp in spans if name in (None, sp.name)])
    got = lib.load_module("metrics", "paged_walk_fill.serve").read(None)
    assert got is None if expected is None else got == pytest.approx(expected)


def test_entry_lists_both_serving_cells():
    entry = {m["name"]: m for m in lib.load_benchmark()["per_layer"]}["paged_walk_fill.serve"]
    assert entry["workloads"] == ["gpt2-large.serve-closed32", "lfm2-8b-a1b-13l.serve-closed32"]
    assert (entry["moves"], entry["better"], entry["unit"]) == ("norm_latency_p50_ms", "higher", "%")
