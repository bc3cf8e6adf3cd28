"""The longcat family as it arrived: files only (a configuration, a cell, a reference,
its counts, one metric, a fault beside the one ``expert_left_out.py`` plants), run
here at the tiny cell of ``tests/data`` (4 of 16 routed experts held from expert 4
on, 8 zero-compute experts, the whole tiny vocabulary). The cell comes out
``correct``; the reference one precision lower, a program that leaves a held
expert's contribution out and one that leaves the identity experts' part out do
not; the configuration's numbers are the preset's and the published ones, the
counts the program's own tree's and a hand count's; the new metric's reader gives
a number from made-up spans, and nothing where its counters are absent."""

import json
import types

import pytest

import expert_left_out
import identity_experts_left_out
from chipbench import hostspans, lib, run
from conftest import TINY, args

CELL = "tiny-longcat.serve"
REAL = "longcat-flash-chat-4l"


@pytest.fixture
def longcat_cell(tiny_cells, monkeypatch):
    """The tiny cells, with the longcat one listed under the serving metrics."""
    listed = json.loads(json.dumps(TINY))
    for metric in listed["end_to_end"]:
        if "tiny-gpt2.serve" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    monkeypatch.setattr(lib, "load_benchmark", lambda: listed)


def test_the_cell_runs_correct_on_the_normal_path(longcat_cell, capsys):
    out = run.execute(args(CELL, seed=2**31 + 11))
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 4
    assert set(out["metrics"]) == {"serve_tokens_per_s", "norm_latency_p50_ms", "setup_s"}
    said = next(json.loads(line) for line in capsys.readouterr().out.splitlines()
                if line.startswith("{") and "facts" in line)
    # two latent rows a layer: 4 sublayers x 128 stored values x 2 bytes
    assert said["facts"]["kv_live_bytes_mean"] == pytest.approx(1024 * said["facts"]["live_tokens_mean"])
    assert said["facts"]["programs"] == {"prefill_insert": 1, "decode_step": 1}


def test_the_control_one_precision_lower_is_not_correct(longcat_cell):
    workload, config = lib.load_cell(CELL)
    driver = lib.load_module("drivers", workload["driver"])
    ctx = run.Context(CELL, workload, config, 5, 1)
    state = driver.setup(ctx)
    driver.window(ctx, state, 1.0, run.WindowHooks(lib.CompileWatch.get()))
    driver.release(state)
    numbers = driver.check(ctx, state)
    control = driver.control_readings(ctx, state)["control_float8"]
    assert run.judge(numbers, workload["limits"])[0], numbers
    assert not run.judge(control, workload["limits"])[0], control


@pytest.mark.parametrize("fault", ["held_expert", "identity_experts"])
def test_a_planted_fault_is_not_correct(longcat_cell, fault):
    """At the tiny size float32 tells each fault from a sound program by the mean
    gap at least; at the cell's size see PERF.md, PR 38."""
    planted = (expert_left_out.planted(expert=4) if fault == "held_expert"
               else identity_experts_left_out.planted())
    with planted:
        out = run.execute(args(CELL, seed=12345))
    assert not out["correct"], out["check"]
    value, limit = out["check"]["logit_gap_mean"]
    assert value > 2 * limit, out["check"]
    assert out["check"]["wrong_answers"] == [0.0, 0.0]  # well-formed, and wrong


# the catalog's row (model-configs guide, architectures.jsonl, LongCat-Flash-Chat)
PUBLISHED = {
    "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "zero_expert_num": 256, "moe_topk": 12,
}


def test_the_configurations_numbers_are_the_presets_and_the_published(tiny_cells):
    from chipbench.program import program_config

    config = lib.load_json("configs", REAL + ".json")
    family, built = program_config(config)  # raises where a stated number differs
    assert (built.num_layers, built.n_routed_experts, built.vocab_size) == (4, 16, 16384)
    assert (built.router_experts, built.first_expert) == (768, 0)
    assert set(config["reduced"]) == {"num_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        want = config["published"].get(key, value) if key in config["reduced"] else config[key]
        assert want == value, key
    assert built.router_experts - built.zero_expert_num == config["published"]["n_routed_experts"]
    entry = next(c for c in json.load(open(lib.ROOT + "/BENCHMARK.json"))["configs"]
                 if c["name"] == REAL)
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    published = type(built).longcat_flash_chat()
    for key in PUBLISHED:
        if key not in config["reduced"]:
            assert getattr(built, key) == getattr(published, key) == config[key], key
    assert built.latent_scales == pytest.approx((2.0, 12 ** 0.5))


def test_counts_agree_with_the_programs_own_tree_and_a_hand_count(tiny_cells):
    from chipbench import weights as weights_lib
    from chipbench.program import program_config

    counts = lib.load_module("counts", "longcat")
    reference = lib.load_module("reference", "longcat")
    for name in ("tiny-longcat", REAL):
        config = lib.load_json("configs", name + ".json")
        family, built = program_config(config)
        model = family.create_longcat(built, abstract=True)
        assert counts.params(config) == model.num_parameters, name
        served = built.serving_family()
        assert counts.kv_layers(config) == served.kv_layers == 2 * built.num_layers
        assert counts.stored_row(config) == served.head_dim and served.kv_heads == 1
        tree = weights_lib.flatten(model.params)
        spec = {n: tuple(s) for n, s, _, _ in reference.weight_spec(config)}
        assert spec == {n: tuple(x.shape) for n, x in tree.items()}, name
    # the cell's configuration, by hand (ISSUE 38): a layer outside its experts
    d = 6144
    mla = 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648 + 1536 + 512
    assert mla == 90_572_800
    outside = 2 * mla + 2 * 226_492_416 + d * 768 + 768 + 4 * d
    assert outside == 638_874_368
    expert = 3 * d * 2048
    assert expert == 37_748_736
    assert counts.params(config) == 4 * (outside + 16 * expert) + 2 * 16384 * d + d == 5_172_749_312
    published = {**config, **{k: config["published"][k] for k in config["reduced"]},
                 "program_keys": {}}
    assert counts.params(published) == 560_664_980_480
    assert counts.kv_layers(config) == 8 and counts.stored_row(config) == 640
    assert counts.kv_bytes_per_token(config) == 8 * 640 * 2 == 10_240
    assert counts.paged_decode_bytes(config, 1000) == 1000 * 640 * 2
    assert counts.held_experts_per_token(config) == pytest.approx(12 * 16 / 768)
    assert counts.moe_expert_bytes(config, 64, 0) == 64 * expert * 2
    # a token's matmuls: 2 MLA, 2 FFNs, the router, a quarter of a held expert; identities none
    body = 4 * (2 * (mla - 2048) + 2 * 226_492_416 + d * 768 + 0.25 * expert)
    assert counts.matmul_flops_per_token(config) == pytest.approx(2 * body)
    assert counts.decode_flops(config, 0) == pytest.approx(2 * body + 2 * d * 16384)
    assert counts.decode_attention_flops(config, 100) == 2 * 64 * (576 + 512) * 100
    assert counts.prefill_attention_flops(config, 512) == pytest.approx(
        512 * 2 * 64 * (192 + 128) * 256.5)


# ------------------------------------------------------------ the metric's reader
def _spans(monkeypatch, attrs):
    made = [types.SimpleNamespace(name="engine.readback", attrs=a) for a in attrs]
    monkeypatch.setattr(hostspans, "session_spans",
                        lambda name=None: [sp for sp in made if name in (None, sp.name)])


def test_the_zero_share_reads_decode_steps_and_nothing_else(tiny_cells, monkeypatch):
    reader = lib.load_module("metrics", "moe_zero_share.serve")
    _spans(monkeypatch, [
        dict(kind="decode", moe_expert_slots=64, moe_zero_pairs=300, moe_pairs=1000),
        dict(kind="decode", moe_expert_slots=64, moe_zero_pairs=340, moe_pairs=1000),
        dict(kind="prefill", moe_expert_slots=64, moe_zero_pairs=9000, moe_pairs=9000),
    ])
    assert reader.read(None) == pytest.approx(32.0)  # 640 of 2,000 decode pairs
    # the parent's program, or a family without zero-compute experts: no counter
    _spans(monkeypatch, [dict(kind="decode", moe_expert_slots=64, moe_assignments=30)])
    assert reader.read(None) is None
    monkeypatch.setattr(hostspans, "session_spans", lambda name=None: None)  # no session
    assert reader.read(None) is None
