"""The loader, the arithmetic, the counts of work and the reduction of a trace."""

import json
import os

import pytest

from chipbench import lib, trace, traffic, work
from chipbench.drivers import serve  # noqa: F401  (a plain import must work too)


def test_loader_finds_files_dropped_in(tmp_path, monkeypatch):
    for kind in ("configs", "workloads", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps({"name": "new-model"}))
    (tmp_path / "workloads" / "new-model.mix.json").write_text(
        json.dumps({"config": "new-model", "driver": "train", "chips": 1}))
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "METRIC = {'name': 'new.metric'}\n\ndef read(run):\n    return 42.0\n")
    monkeypatch.setattr(lib, "DATA_DIRS", [lib.HERE, str(tmp_path)])
    workload, config = lib.load_cell("new-model.mix")
    assert config["name"] == "new-model" and workload["driver"] == "train"
    assert lib.load_module("metrics", "new.metric").read(None) == 42.0
    with pytest.raises(lib.BenchError):
        lib.load_cell("no-such.cell")


def test_every_cell_and_metric_of_the_benchmark_has_its_files():
    benchmark = lib.load_benchmark()
    for cell in benchmark["workloads"]:
        workload, config = lib.load_cell(cell["name"])
        assert workload["config"] == cell["config"] == config["name"]
        assert workload["chips"] == cell["chips"]
        lib.load_module("drivers", workload["driver"])
        lib.load_module("reference", config["reference"])
        end_to_end, per_layer = lib.cell_metrics(benchmark, cell["name"])
        assert {m["name"] for m in end_to_end} >= {"setup_s"} and len(end_to_end) >= 2
        assert per_layer
    for entry in benchmark["per_layer"]:
        meta = lib.load_module("metrics", entry["name"]).METRIC
        for key in ("name", "layer", "unit", "moves", "source"):
            assert meta[key] == entry[key], (entry["name"], key)
    for entry in benchmark["configs"]:
        assert os.path.isfile(os.path.join(lib.ROOT, entry["file"]))


def test_percentile_matches_numpy():
    import numpy as np

    values = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 50, 95, 100):
        assert lib.percentile(values, q) == pytest.approx(float(np.percentile(values, q)))
    with pytest.raises(ValueError):
        lib.percentile([], 50)


def test_work_counts_agree_with_a_hand_count():
    mistral = lib.load_json("configs", "mistral-7b-2l.json")
    gpt2 = lib.load_json("configs", "gpt2-large.json")
    llama_counts, gpt2_counts = lib.load_module("counts", "llama"), lib.load_module("counts", "gpt2")
    # a configuration's counts are found by its family where it names no file of its own
    assert lib.count(mistral, "params") is llama_counts.params
    assert lib.count(gpt2, "params") is gpt2_counts.params
    assert llama_counts.params(mistral) == 698_372_096
    assert gpt2_counts.params(gpt2) == 774_030_080
    # one Mistral layer, a token: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate, up and down 4096x14336, two FLOPs a weight
    layer = 2 * (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    assert llama_counts.layer_matmul_flops_per_token(mistral) == layer
    # causal attention at 2048 tokens: a query sees 1024.5 keys on average
    attention = 4 * 4096 * 1024.5
    head = 2 * 4096 * 32000 * 2047 / 2048
    assert llama_counts.train_flops_per_token(mistral, 2048) == pytest.approx(
        3 * (2 * (layer + attention) + head))
    assert work.mean_keys(8, window=4) == pytest.approx((1 + 2 + 3 + 4 + 4 * 4) / 8)
    # GPT-2 large, a decoded token with 200 keys in its cache
    per_layer = 2 * (4 * 1280 * 1280 + 2 * 1280 * 5120) + 4 * 1280 * 200
    assert gpt2_counts.decode_flops(gpt2, 200) == pytest.approx(36 * per_layer + 2 * 1280 * 50257)
    assert work.kv_bytes_per_token_per_layer(gpt2) == 2 * 20 * 64 * 2
    flash = llama_counts.flash_train_work(mistral, 1, 2048)
    assert flash["flops"] == pytest.approx(2 * 3 * 2048 * attention)
    # every layer of both families keeps keys and values: 8 KV heads of 128 and
    # 20 heads of 64, keys and values, two bytes each
    assert llama_counts.kv_layers(mistral) == 2 and gpt2_counts.kv_layers(gpt2) == 36
    assert llama_counts.kv_bytes_per_token(mistral) == 2 * 4096
    assert gpt2_counts.kv_bytes_per_token(gpt2) == 36 * 5120
    # one call of the decode kernel reads one layer's keys and values of the live tokens
    assert gpt2_counts.paged_decode_bytes(gpt2, 1000) == 1000 * 5120


def test_trace_reduction_on_a_synthetic_trace():
    ev = trace.Event
    device = trace.DeviceTrace(
        ops=[
            ev("while.1", 0, 1000),  # holds the two fusions
            ev("fusion.1", 100, 300),
            ev("fusion.2", 500, 400),
            ev("flash_fwd", 1500, 500),  # after a gap of 500
            ev("fusion.1", 2000, 1000),
        ],
        modules=[ev("jit_fused(123)", 0, 1000), ev("jit_fused(123)", 1500, 1500),
                 ev("jit_other(9)", 0, 10)],
    )
    summary = trace.summarize([device])
    assert summary.window_s == pytest.approx(3000e-9)
    assert summary.busy_s == pytest.approx(2500e-9)
    assert summary.idle_share == pytest.approx(500 / 3000)
    assert summary.op_self_s["while.1"] == pytest.approx(300e-9)  # 1000 less 300 and 400
    assert summary.op_self_s["fusion.1"] == pytest.approx(1300e-9)
    assert trace.time_matching(summary, r"flash") == (pytest.approx(500e-9), 1)
    assert trace.programs_matching(summary, r"^jit_fused$") == [pytest.approx(1e-6), pytest.approx(1.5e-6)]
    assert summary.gaps[0][0] == pytest.approx(500e-9)
    out = trace.breakdown(summary)
    assert out["device_ops"][0][0] == "fusion.1" and len(out["idle_gaps"]) == 1
    with pytest.raises(ValueError):
        trace.summarize([trace.DeviceTrace(ops=[], modules=[])])


def test_traffic_gives_every_seed_the_same_sizes_in_another_order():
    params = lib.load_json("workloads", "gpt2-large.serve-closed32.json")["traffic"]
    a = traffic.request_pool(params, 50257, 1)
    b = traffic.request_pool(params, 50257, 2**31 + 9)
    again = traffic.request_pool(params, 50257, 1)
    period = params["pool"]
    assert len(a) == params["requests"] and len(a) % period == 0
    for cycle in range(0, 3 * period, period):  # every round holds the same sizes
        assert sorted(len(r.prompt) for r in a[cycle:cycle + period]) == sorted(
            len(r.prompt) for r in b[:period])
        assert sorted(r.max_new_tokens for r in a[cycle:cycle + period]) == sorted(
            r.max_new_tokens for r in b[:period])
    # and the same pairs: which prompt meets which output, and of which kind, is
    # work too (what is live, and for how long), so no seed may deal it anew
    pairs = [sorted((len(r.prompt), r.max_new_tokens, r.greedy) for r in pool[:period])
             for pool in (a, b, a[period:])]
    assert pairs[0] == pairs[1] == pairs[2]
    standing = [sorted((len(r.prompt), r.max_new_tokens, r.greedy)
                       for r in traffic.standing_requests(params, 50257, seed))
                for seed in (1, 2**31 + 9)]
    assert standing[0] == standing[1]
    # the pairing leaves no order behind: long prompts meet short outputs and long ones
    prompts, outputs, _ = traffic.paired_sizes(params, period)
    product = float((prompts * outputs).sum()) / (period * prompts.mean() * outputs.mean())
    assert product == pytest.approx(1.0, abs=0.01)
    assert sorted(traffic.pairing(period)) == list(range(period))
    assert not (a[0].prompt == a[period].prompt).all()  # the same size, fresh ids
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all((x.prompt == y.prompt).all() and x.seed == y.seed for x, y in zip(a, again))
    assert sum(r.greedy for r in a) == len(a) // 2
    lens = [len(r.prompt) for r in a]
    assert min(lens) >= 16 and max(lens) <= 512
    assert 88 <= lib.median(lens) <= 104
    assert all(16 <= r.max_new_tokens <= 384 for r in a)


def test_serve_accounting_counts_the_tokens_made_inside_the_window():
    import types

    from chipbench.drivers import serve as driver

    def record(sent, done, new, prompt=10, failed=False):
        r = driver.Record(types.SimpleNamespace(prompt=[0] * prompt, max_new_tokens=new), 0, sent)
        r.done, r.failed = done, failed
        r.result = types.SimpleNamespace(ttft_s=0.1, latency_s=done - sent, queue_wait_s=0.01)
        return r

    ctx = types.SimpleNamespace(config=lib.load_json("configs", "gpt2-large.json"))
    records = [
        record(0.0, 2.0, 100),   # half of its life inside: 50 tokens
        record(1.0, 3.0, 40),    # all inside: 40
        record(9.0, 11.0, 60),   # half inside: 30; came back after the window
        record(2.0, 4.0, 10, failed=True),  # no tokens, and the worst latency
    ]
    out = driver.account(ctx, records[:3], 1.0, 10.0, {"slots": 4})
    assert out["tokens"] == pytest.approx(120.0)
    assert out["end_to_end"]["serve_tokens_per_s"] == pytest.approx(120.0 / 9.0)
    assert out["attempted"] == 2 and out["failed"] == 0
    # 20 ms and 50 ms a token came back inside the window
    assert out["end_to_end"]["norm_latency_p50_ms"] == pytest.approx(35.0)
    assert out["facts"]["tokens_of_requests_back_per_s"] == pytest.approx(140.0 / 9.0)
    # with the failed one the 90th percentile lies on the way to the worst
    with pytest.raises(lib.BenchError):
        driver.account(ctx, records, 1.0, 10.0, {"slots": 4})
