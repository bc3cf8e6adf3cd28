"""A model family that the harness has never seen arrives as files: its counts are
found by the name its configuration gives, a count it lacks ends the run (or
silences the one reader that asked), and a kernel is matched by its name, so that
a model's own kernel is nobody's until it brings its metric.

Nothing under ``chipbench/`` is patched here but ``lib.DATA_DIRS``, which gains a
directory: had these been files of a later PR, ``git diff --stat chipbench/``
would list additions only."""

import json
import re
import types

import pytest

from chipbench import lib, run, trace
from conftest import args


def test_a_configuration_brings_its_own_counts_as_a_file(tiny_cells, capsys):
    """``tiny-hybrid`` runs the program of the gpt2 family and names
    ``counts/toy-hybrid.py``: the window's FLOPs, what the cache is said to hold
    and the shapes a pool copy would have all come from that file."""
    out = run.execute(args("tiny-hybrid.serve", seed=2**31 + 29))
    assert out["correct"], out["check"]
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    said = next(line for line in lines if "facts" in line)
    facts, window = said["facts"], said["window"]
    workload, config = lib.load_cell("tiny-hybrid.serve")
    toy, family = lib.load_module("counts", "toy-hybrid"), lib.load_module("counts", "gpt2")
    assert lib.count(config, "decode_flops") is toy.decode_flops

    tokens = out["metrics"]["serve_tokens_per_s"]["value"] * window
    assert tokens > 0
    assert facts["model_flops"] == pytest.approx(toy.TOKEN_FLOPS * tokens)
    # one layer of 4 heads of 16, keys and values, in bf16; the family's own count has two
    assert toy.kv_bytes_per_token(config) == 2 * 4 * 16 * 2 == family.kv_bytes_per_token(config) // 2
    assert facts["live_tokens_mean"] > 0 and facts["reserved_tokens_mean"] > 0
    assert facts["kv_live_bytes_mean"] == pytest.approx(256 * facts["live_tokens_mean"])
    assert facts["kv_reserved_bytes_mean"] == pytest.approx(256 * facts["reserved_tokens_mean"])

    # 4 slots x 64 positions in blocks of 16, and one block to spare: 17 blocks
    pool = re.compile(lib.load_module("metrics", "kv_pool_copy_share.serve")
                      .pattern(config, workload["serving"]))
    for shape in ("1,17,16,64", "1,17,16,4,16", "17,16,64"):
        assert pool.search(f"%copy.3 = bf16[{shape}]{{3,2,1,0}} copy(bf16[{shape}] %p)"), shape
    for shape in ("2,17,16,64", "34,16,64", "1,17,16,128"):  # two layers' worth is not this file's pool
        assert not pool.search(f"%copy.3 = bf16[{shape}]{{3,2,1,0}} copy(bf16[{shape}] %p)"), shape


@pytest.fixture
def cell_without_decode_flops(tiny_cells, tmp_path, monkeypatch):
    """The tiny serving cell under a configuration whose counts file lacks
    ``decode_flops``, as files in a directory of their own."""
    workload, config = lib.load_cell("tiny-gpt2.serve")
    for kind in ("counts", "configs", "workloads"):
        (tmp_path / kind).mkdir()
    (tmp_path / "counts" / "toy-partial.py").write_text(
        "def prefill_flops(cfg, prompt_len):\n    return 1.0\n\n\n"
        "def kv_bytes_per_token(cfg, itemsize=2):\n    return 64\n")
    (tmp_path / "configs" / "tiny-partial.json").write_text(
        json.dumps({**config, "name": "tiny-partial", "counts": "toy-partial"}))
    (tmp_path / "workloads" / "tiny-partial.serve.json").write_text(
        json.dumps({**workload, "name": "tiny-partial.serve", "config": "tiny-partial"}))
    monkeypatch.setattr(lib, "DATA_DIRS", lib.DATA_DIRS + [str(tmp_path)])
    return "tiny-partial.serve"


def test_a_count_the_file_lacks_ends_the_run_by_name(cell_without_decode_flops):
    with pytest.raises(lib.BenchError, match=r"chipbench/counts/toy-partial\.py has no decode_flops\(\)"):
        run.execute(args(cell_without_decode_flops))
    with pytest.raises(lib.BenchError, match=r"no file chipbench/counts/no-such-family\.py"):
        lib.count({"family": "no-such-family"}, "params")


def test_a_reader_whose_count_is_missing_reports_nothing(cell_without_decode_flops):
    """The same trace read under two configurations: the reader that needs
    ``decode_flops`` gives a share where the counts file has it and nothing,
    never another family's number, where it has not."""
    step = trace.Event
    summary = trace.summarize([trace.DeviceTrace(
        ops=[step("%fusion.1 = bf16[4,64] fusion()", 0, 1_000_000)],
        modules=[step("jit__decode_impl(7)", 0, 1_000_000)])])
    result = {"counters": {"live_slots_mean": 4.0, "live_tokens_mean": 80.0}}
    reader = lib.load_module("metrics", "mfu_decode.serve")

    def share(cell):
        workload, config = lib.load_cell(cell)
        ctx = types.SimpleNamespace(config=config, workload=workload,
                                    peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        return reader.read(types.SimpleNamespace(ctx=ctx, result=result, summary=summary))

    assert share("tiny-gpt2.serve") > 0
    assert share(cell_without_decode_flops) is None


# the chip's names of the program's kernels (my chip runs, PR 27 and 28) and a
# kernel that a later model might bring, all under the one custom-call target:
# (instruction, result, microseconds)
KERNEL_EVENTS = [
    ("flash_fwd.16", "(bf16[32,2048,128]{2,1,0}, f32[32,1,2048]{2,1,0})", 4),
    ("flash_fwd.15", "(bf16[32,2048,128]{2,1,0}, f32[32,1,2048]{2,1,0})", 4),
    ("flash_bwd_dq.10", "bf16[32,2048,128]{2,1,0}", 5),
    ("flash_bwd_dkv.10", "(bf16[8,2048,128]{2,1,0}, bf16[8,2048,128]{2,1,0})", 6),
    ("paged_decode.9", "bf16[32,20,1,64]{3,2,1,0}", 7),
    ("paged_verify.2", "bf16[32,20,4,64]{3,2,1,0}", 8),
    ("fused_sample.1", "s32[32,1]{1,0}", 9),
    ("grouped_expert_matmul.3", "bf16[32,4,1,64]{3,2,1,0}", 10),
]


@pytest.mark.parametrize("metric, constant, microseconds, calls", [
    ("flash_attention_roofline.train", "KERNELS", 4 + 4 + 5 + 6, 4),
    ("paged_decode_roofline.serve", "KERNEL", 7, 1),
    ("sampler_ms_p50.serve", "KERNEL", 9, 1),
])
def test_a_kernel_metric_takes_the_kernels_of_its_name_and_no_other(metric, constant, microseconds, calls):
    events = [
        trace.Event(f'%{instruction} = {result} custom-call(s32[32,64]{{1,0}} %tables, bf16[32,2048] %x), '
                    'custom_call_target="tpu_custom_call"', 20_000 * index, 1000 * length)
        for index, (instruction, result, length) in enumerate(KERNEL_EVENTS)
    ]
    summary = trace.summarize([trace.DeviceTrace(ops=events, modules=[])])
    seconds, found = trace.time_matching(summary, getattr(lib.load_module("metrics", metric), constant))
    assert (seconds, found) == (pytest.approx(1e-6 * microseconds), calls)
