"""The axk1 family as it arrived: files only (a configuration, a cell, a reference,
its counts, two metrics, a fault beside the one ``expert_left_out.py`` plants), run here at the tiny cell of ``tests/data``
(4 of 16 experts held from expert 4 on, the whole tiny vocabulary). The cell comes
out ``correct``; the reference one precision lower, a program that leaves a held
expert's contribution out and one that leaves the rotary key out of the scores do
not; the configuration's numbers are the preset's and the counts the program's own
tree's; and each new metric's reader gives a number from a made-up run, and nothing
where its operation or its counter is absent."""

import json
import types

import pytest

import expert_left_out
import rope_key_left_out
from chipbench import hostspans, lib, run, trace
from conftest import TINY, args

CELL = "tiny-axk1.serve"
REAL = "ax-k1-7l"


@pytest.fixture
def axk1_cell(tiny_cells, monkeypatch):
    """The tiny cells, with the axk1 one listed under the serving metrics."""
    listed = json.loads(json.dumps(TINY))
    for metric in listed["end_to_end"]:
        if "tiny-gpt2.serve" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    monkeypatch.setattr(lib, "load_benchmark", lambda: listed)


def _driver_run(seed):
    workload, config = lib.load_cell(CELL)
    driver = lib.load_module("drivers", workload["driver"])
    ctx = run.Context(CELL, workload, config, seed, 1)
    state = driver.setup(ctx)
    driver.window(ctx, state, 1.0, run.WindowHooks(lib.CompileWatch.get()))
    driver.release(state)
    return workload, driver, ctx, state


def test_the_cell_runs_correct_on_the_normal_path(axk1_cell, capsys):
    out = run.execute(args(CELL, seed=2**31 + 7))
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 4
    assert set(out["metrics"]) == {"serve_tokens_per_s", "norm_latency_p50_ms", "setup_s"}
    assert set(out["check"]) == {"logit_gap", "sample_gap", "logit_gap_mean", "wrong_answers"}
    said = next(json.loads(line) for line in capsys.readouterr().out.splitlines()
                if line.startswith("{") and "facts" in line)
    # the cache is said to hold one row a layer: 3 layers x 128 stored values x 2 bytes
    assert said["facts"]["kv_live_bytes_mean"] == pytest.approx(768 * said["facts"]["live_tokens_mean"])
    assert said["facts"]["programs"] == {"prefill_insert": 1, "decode_step": 1}


def test_the_control_one_precision_lower_is_not_correct(axk1_cell):
    workload, driver, ctx, state = _driver_run(seed=5)
    numbers = driver.check(ctx, state)
    control = driver.control_readings(ctx, state)["control_float8"]
    assert run.judge(numbers, workload["limits"])[0], numbers
    assert not run.judge(control, workload["limits"])[0], control


@pytest.mark.parametrize("fault", ["held_expert", "rope_key"])
def test_a_planted_fault_is_not_correct(axk1_cell, fault):
    """The issue's two faults. At the tiny size float32 tells each from a sound
    program by the mean gap at least; at the cell's size see PERF.md, PR 35."""
    planted = (expert_left_out.planted(expert=4) if fault == "held_expert"
               else rope_key_left_out.planted())
    with planted:
        out = run.execute(args(CELL, seed=12345))
    assert not out["correct"], out["check"]
    value, limit = out["check"]["logit_gap_mean"]
    assert value > 2 * limit, out["check"]
    assert out["check"]["wrong_answers"] == [0.0, 0.0]  # well-formed, and wrong


def test_the_configurations_numbers_are_the_presets(tiny_cells):
    from chipbench.program import program_config

    config = lib.load_json("configs", REAL + ".json")
    family, built = program_config(config)  # raises where a stated number differs
    assert (built.num_hidden_layers, built.n_routed_experts, built.vocab_size) == (7, 8, 20480)
    assert (built.router_experts, built.first_expert) == (192, 0)
    assert config["published"]["n_routed_experts"] == built.router_experts
    assert set(config["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert built.rope_scaling == config["rope_scaling"]
    entry = next(c for c in json.load(open(lib.ROOT + "/BENCHMARK.json"))["configs"]
                 if c["name"] == REAL)
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    # every width as published: what reduced lists is depth, experts held and vocabulary
    published = type(built).ax_k1()
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "num_attention_heads", "num_experts_per_tok", "n_group", "topk_group"):
        assert getattr(built, key) == getattr(published, key) == config[key], key


def test_counts_agree_with_the_programs_own_tree(tiny_cells):
    from chipbench.program import program_config

    counts = lib.load_module("counts", "axk1")
    reference = lib.load_module("reference", "axk1")
    for name in ("tiny-axk1", REAL):
        config = lib.load_json("configs", name + ".json")
        family, built = program_config(config)
        model = family.create_axk1(built, abstract=True)
        assert counts.params(config) == model.num_parameters, name
        served = built.serving_family()
        assert counts.kv_layers(config) == served.kv_layers
        assert counts.stored_row(config) == served.head_dim and served.kv_heads == 1
        assert counts.kv_bytes_per_token(config) == served.kv_layers * served.head_dim * 2
        # the reference's list of leaves is the program's tree, name for name
        import jax
        from chipbench import weights as weights_lib
        tree = weights_lib.flatten(model.params)
        spec = {n: tuple(s) for n, s, _, _ in reference.weight_spec(config)}
        assert spec == {n: tuple(x.shape) for n, x in tree.items()}, name
    # the cell's configuration, by hand: 7.57e9 B of bfloat16, one 640-wide row a layer
    assert counts.params(config) == 3_784_367_104
    assert counts.latent_row(config) == 576 and counts.stored_row(config) == 640
    assert counts.kv_bytes_per_token(config) == 7 * 640 * 2 == 8960
    assert counts.paged_decode_bytes(config, 1000) == 1000 * 640 * 2  # a live row once
    d, im = 7168, 2048
    assert counts.held_experts_per_token(config) == pytest.approx(8 * 8 / 192)
    assert counts.moe_expert_bytes(config, 48, 0) == 6 * 8 * 3 * d * im * 2  # every held expert once
    attention = 7 * 101_122_048
    body = attention + 3 * d * 18432 + 6 * (d * 192 + (1 + 1 / 3) * 3 * d * im)
    assert counts.matmul_flops_per_token(config) == pytest.approx(2 * body)
    assert counts.decode_flops(config, 0) == pytest.approx(2 * body + 2 * d * 20480)
    # absorbed: 576 + 512 a row a head; up-projected: 192 + 128 a key a head
    assert counts.decode_attention_flops(config, 100) == 2 * 64 * (576 + 512) * 100
    assert counts.prefill_attention_flops(config, 1024) == pytest.approx(
        1024 * 2 * 64 * (192 + 128) * 512.5)


# ------------------------------------------------------------ the metrics' readers
OPS = [
    ('%paged_decode.9 = bf16[64,64,512]{2,1,0} custom-call(s32[64,256] %t, s32[64] %p, '
     'bf16[64,64,640] %q, bf16[114695,16,640] %k), custom_call_target="tpu_custom_call"', 300),
    ('%moe_gmm.3 = f32[128,2048]{1,0} custom-call(s32[9] %g, bf16[128,7168] %x, '
     'bf16[48,7168,2048] %w), custom_call_target="tpu_custom_call"', 600),
    ("%fusion.7 = bf16[64,7168] fusion(bf16[64,7168] %x)", 100),
]


def _made_up_run(ops, spans, monkeypatch, config=REAL):
    events = [trace.Event(name, 1_000_000 * index, 1000 * microseconds)
              for index, (name, microseconds) in enumerate(ops)]
    summary = trace.summarize([trace.DeviceTrace(ops=events, modules=[])])
    made = [types.SimpleNamespace(name="engine.decode_step", attrs=attrs) for attrs in spans]
    monkeypatch.setattr(hostspans, "session_spans",
                        lambda name=None: [sp for sp in made if name in (None, sp.name)])
    ctx = types.SimpleNamespace(config=lib.load_json("configs", config + ".json"), workload={},
                                peaks={"hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(ctx=ctx, result={"counters": {"live_tokens_mean": 90_000.0}},
                                 summary=summary)


STEP = dict(live=64, decoding=64, slots=64, kv_live_tokens=90_000, kv_row_bytes=8960)


def test_each_new_reader_gives_a_number_from_a_made_up_run(tiny_cells, monkeypatch):
    made = _made_up_run(OPS, [STEP, STEP], monkeypatch)
    share = lib.load_module("metrics", "latent_attention_share.serve").read(made)
    assert share == pytest.approx(30.0)  # 300 of 1000 busy microseconds
    assert lib.load_module("metrics", "kv_row_bytes.serve").read(made) == 8960
    # the accepted roofline reads the same kernel against a live row counted once
    roofline = lib.load_module("metrics", "paged_decode_roofline.serve").read(made)
    assert roofline == pytest.approx(100 * (90_000 * 640 * 2 / 819e9) / 300e-6)


def test_each_new_reader_is_silent_where_its_source_is_absent(tiny_cells, monkeypatch):
    """The parent commit's program, or another family's: no counter on any span,
    no kernel of the name, no latent cache in the configuration's counts."""
    no_counter = _made_up_run(OPS, [dict(live=64, kv_live_tokens=90_000)], monkeypatch)
    assert lib.load_module("metrics", "kv_row_bytes.serve").read(no_counter) is None
    no_kernel = _made_up_run(OPS[1:], [STEP], monkeypatch)
    assert lib.load_module("metrics", "latent_attention_share.serve").read(no_kernel) is None
    other_family = _made_up_run(OPS, [STEP], monkeypatch, config="gpt2-large")
    assert lib.load_module("metrics", "latent_attention_share.serve").read(other_family) is None
    monkeypatch.setattr(hostspans, "session_spans", lambda name=None: None)  # no session at all
    assert lib.load_module("metrics", "kv_row_bytes.serve").read(other_family) is None
