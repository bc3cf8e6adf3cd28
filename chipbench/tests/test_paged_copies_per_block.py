"""``paged_copies_per_block.serve`` on hand-made session spans: the mean of copies
over live blocks, and nothing where the program's spans lack the counters (the
parent of the change that brought them, a dense cache) or there is no session."""

import types

import pytest

from chipbench import hostspans, lib


def decode_step(**attrs):
    return types.SimpleNamespace(name="engine.decode_step", t0=0.0, t1=0.001, id=1, parent=0,
                                 attrs=dict(decoding=4, slots=4, kv_live_tokens=600, **attrs))


WITH = [decode_step(kv_live_blocks=40, kv_copy_descriptors=6),
        decode_step(kv_live_blocks=42, kv_copy_descriptors=42)]
WITHOUT = [decode_step(kv_walked_tokens=768)]
DENSE = [decode_step(kv_live_blocks=0, kv_copy_descriptors=0)]


@pytest.mark.parametrize("spans,expected", [
    (WITH, 100 * (6 / 40 + 42 / 42) / 2), (WITHOUT, None), (DENSE, None), ([], None), (None, None),
], ids=["counted", "parent_has_no_counter", "no_block_pool", "empty_session", "no_session"])
def test_reader(monkeypatch, spans, expected):
    monkeypatch.setattr(
        hostspans, "session_spans",
        lambda name=None: None if spans is None else [sp for sp in spans if name in (None, sp.name)])
    got = lib.load_module("metrics", "paged_copies_per_block.serve").read(None)
    assert got is None if expected is None else got == pytest.approx(expected)


def test_entry_reads_serving_cells_of_the_benchmark():
    benchmark = lib.load_benchmark()
    entry = {m["name"]: m for m in benchmark["per_layer"]}["paged_copies_per_block.serve"]
    cells = {w["name"] for w in benchmark["workloads"]}
    serving = {name for m in benchmark["end_to_end"] if m["name"] == entry["moves"]
               for name in m["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells & serving
    assert (entry["moves"], entry["better"], entry["unit"], entry["source"]) == (
        "norm_latency_p50_ms", "lower", "%", "program_counter")
    assert entry["layer"] == "serving kernels"
