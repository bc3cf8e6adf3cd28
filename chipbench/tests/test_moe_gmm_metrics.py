"""``moe_gmm_roofline.serve`` and ``moe_gmm_share.serve`` on a made-up trace summary:
the program's own grouped-matmul kernel is counted by its name, the compiler's
``ragged-dot`` kernels are not; nothing where no ``moe_gmm`` ran (the parent of the
PR that brought the kernel), where the spans carry no counters, for a family
without experts, and without a session."""

import types

import pytest

from chipbench import hostspans, lib, trace

NEW = ("moe_gmm_roofline.serve", "moe_gmm_share.serve")
OLD = ("moe_experts_roofline.serve", "moe_experts_share.serve")

GMM = [
    ('%moe_gmm.3 = f32[128,1792]{1,0} custom-call(s32[33] %g, s32[33] %t, s32[33] %lo, s32[33] %hi, '
     'bf16[128,2048] %x, bf16[384,2048,1792] %w), custom_call_target="tpu_custom_call"', 400),
    ('%moe_gmm = f32[128,2048]{1,0} custom-call(s32[33] %g, s32[33] %t, s32[33] %lo, s32[33] %hi, '
     'bf16[128,1792] %h, bf16[384,1792,2048] %w), custom_call_target="tpu_custom_call"', 200),
]
NOT_GMM = [
    ('%ragged-dot-metadata.1 = (s32[385], s32[387]) custom-call(s32[384] %sizes), '
     'custom_call_target="tpu_custom_call"', 50),
    # a fusion that reads the kernel's result, and one whose name only starts alike
    ("%fusion.7 = bf16[128,1792] fusion(f32[128,1792] %moe_gmm.3)", 250),
    ('%moe_gmm_visits.2 = s32[33] fusion(s32[32] %sizes)', 100),
]
PARENT = [
    ('%ragged-dot-none.3 = f32[128,1792]{1,0} custom-call(s32[1] %m, bf16[128,2048] %x, '
     'bf16[384,2048,1792] %w), custom_call_target="tpu_custom_call"', 600),
] + NOT_GMM

STEP = dict(kind="decode", moe_assignments=1536, moe_experts_touched=360,
            moe_expert_slots=384, moe_load_max=12)
PREFILL = dict(STEP, kind="prefill", moe_assignments=24576)


def made_up_run(ops, spans, monkeypatch, config="lfm2-8b-a1b-13l.json"):
    events = [trace.Event(name, 1_000_000 * index, 1000 * microseconds)
              for index, (name, microseconds) in enumerate(ops)]
    summary = trace.summarize([trace.DeviceTrace(ops=events, modules=[])])
    made = None if spans is None else [
        types.SimpleNamespace(name="engine.readback", attrs=attrs) for attrs in spans]
    monkeypatch.setattr(
        hostspans, "session_spans",
        lambda name=None: None if made is None else [sp for sp in made if name in (None, sp.name)])
    ctx = types.SimpleNamespace(config=lib.load_json("configs", config), workload={},
                                peaks={"hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(ctx=ctx, result={}, summary=summary)


def read(name, run):
    return lib.load_module("metrics", name).read(run)


def test_the_kernel_is_counted_by_its_name_and_nothing_else_is(monkeypatch):
    run = made_up_run(GMM + NOT_GMM, [STEP, PREFILL], monkeypatch)
    moved = lib.load_module("counts", "lfm2").moe_expert_bytes(run.ctx.config, 720, 1536 + 24576)
    assert read("moe_gmm_roofline.serve", run) == pytest.approx(100 * (moved / 819e9) / 600e-6)
    assert read("moe_gmm_share.serve", run) == pytest.approx(100 * 600 / 1000)
    # the two that matched what the compiler made of ragged_dot now report nothing
    assert [read(name, run) for name in OLD] == [None, None]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("ops,spans,config", [
    (PARENT, [STEP], "lfm2-8b-a1b-13l.json"),
    (NOT_GMM, [STEP], "lfm2-8b-a1b-13l.json"),
    ([("%paged_decode.9 = bf16[32,32,1280] custom-call(bf16[32,32,1280] %q)", 90)], [],
     "gpt2-large.json"),
], ids=["parent_runs_ragged_dot", "no_grouped_matmul", "another_family"])
def test_no_moe_gmm_line_reads_none(monkeypatch, ops, spans, config, name):
    assert read(name, made_up_run(ops, spans, monkeypatch, config)) is None


@pytest.mark.parametrize("spans,config", [
    ([dict(kind="decode", popped=1)], "lfm2-8b-a1b-13l.json"),
    ([], "lfm2-8b-a1b-13l.json"),
    (None, "lfm2-8b-a1b-13l.json"),
    ([STEP], "gpt2-large.json"),
], ids=["spans_without_counters", "empty_session", "no_session", "family_without_experts"])
def test_the_roofline_needs_the_counters_and_the_familys_bytes(monkeypatch, spans, config):
    run = made_up_run(GMM, spans, monkeypatch, config)
    assert read("moe_gmm_roofline.serve", run) is None
    assert read("moe_gmm_share.serve", run) == pytest.approx(100.0)  # the trace alone


def test_the_parents_readers_still_read_the_parent(monkeypatch):
    run = made_up_run(PARENT, [STEP], monkeypatch)
    assert read("moe_experts_share.serve", run) == pytest.approx(100 * 600 / 1000)
    assert read("moe_experts_roofline.serve", run) is not None


@pytest.mark.parametrize("name,better", [("moe_gmm_roofline.serve", "higher"),
                                         ("moe_gmm_share.serve", "lower")])
def test_entries_follow_the_files_they_succeed(name, better):
    entries = {m["name"]: m for m in lib.load_benchmark()["per_layer"]}
    entry, old = entries[name], entries[name.replace("moe_gmm", "moe_experts")]
    assert entry["better"] == better == old["better"]
    assert entry["workloads"] == ["lfm2-8b-a1b-13l.serve-closed32"] == old["workloads"]
    assert {k: entry[k] for k in ("layer", "moves", "unit", "source")} == \
        {k: old[k] for k in ("layer", "moves", "unit", "source")}
    metric = lib.load_module("metrics", name).METRIC
    assert {k: entry[k] for k in metric} == metric
