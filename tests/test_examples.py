"""Example smoke tests (role of reference tests/test_examples.py): every
example must run end-to-end in tiny mode inside the virtual mesh."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(_ROOT, "examples")

_ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": _ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
}


def _run(script, *args, timeout=420):
    return subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        env=_ENV,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


@pytest.mark.slow
def test_nlp_example_tiny(tmp_path):
    result = _run("nlp_example.py", "--tiny", "--epochs", "1", "--batch_size", "16")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "epoch 0" in result.stdout


@pytest.mark.slow
def test_llama_finetune_tiny():
    result = _run("llama_finetune.py", "--preset", "tiny", "--steps", "4", "--seq_len", "64")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "tokens/s" in result.stdout


@pytest.mark.slow
def test_gradient_accumulation_example():
    result = _run(os.path.join("by_feature", "gradient_accumulation.py"))
    assert result.returncode == 0, result.stderr[-2000:]
    assert "synced=True" in result.stdout
    assert "synced=False" in result.stdout


@pytest.mark.slow
def test_local_sgd_example():
    result = _run("by_feature/local_sgd.py", "--steps", "4")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "averaged across data shards" in result.stdout


@pytest.mark.slow
def test_early_stopping_example():
    result = _run("by_feature/early_stopping.py", "--epochs", "3")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "epoch=2" in result.stdout or "early stop" in result.stdout


@pytest.mark.slow
def test_memory_example():
    result = _run("by_feature/memory.py", "--starting_batch_size", "16", "--steps", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "fit at batch_size" in result.stdout


@pytest.mark.slow
def test_fault_tolerance_example(tmp_path):
    result = _run(
        "by_feature/fault_tolerance.py",
        "--project_dir", str(tmp_path),
        "--total_steps", "6", "--save_every", "3",
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert "training complete" in result.stdout


@pytest.mark.slow
def test_tracking_example(tmp_path):
    result = _run("by_feature/tracking.py", "--project_dir", str(tmp_path))
    assert result.returncode == 0, result.stderr[-2000:]
    assert "logged 8 steps" in result.stdout
    assert any(f.suffix == ".jsonl" for f in tmp_path.rglob("*")), "no JSONL log written"


@pytest.mark.slow
def test_automatic_gradient_accumulation_example():
    result = _run(
        "by_feature/automatic_gradient_accumulation.py",
        "--target_effective_batch", "32",
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert "trained with per-step batch" in result.stdout


@pytest.mark.slow
def test_schedule_free_example():
    result = _run("by_feature/schedule_free.py", "--epochs", "1")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "epoch 0 loss=" in result.stdout


@pytest.mark.slow
def test_ddp_comm_hook_example():
    result = _run("by_feature/ddp_comm_hook.py", "--comm_hook", "bf16")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "comm_hook=bf16" in result.stdout


@pytest.mark.slow
def test_ddp_comm_hook_powersgd_example():
    result = _run("by_feature/ddp_comm_hook.py", "--comm_hook", "powersgd")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "comm_hook=powersgd" in result.stdout


@pytest.mark.slow
def test_pipeline_parallelism_example():
    result = _run(
        "by_feature/pipeline_parallelism.py",
        "--pp", "2", "--virtual", "2", "--steps", "2",
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert "pp=2 virtual=2" in result.stdout


@pytest.mark.slow
def test_fsdp_peak_mem_example():
    result = _run("by_feature/fsdp_with_peak_mem_tracking.py", "--steps", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "after prepare" in result.stdout


@pytest.mark.slow
def test_cross_validation_example():
    result = _run("by_feature/cross_validation.py", "--folds", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "mean accuracy over 2 folds" in result.stdout


@pytest.mark.slow
def test_gpt_pretraining_example():
    result = _run(
        "by_feature/gpt_pretraining.py",
        "--tp", "2", "--dp_shard", "4", "--steps", "4",
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert "tok/s" in result.stdout


@pytest.mark.slow
def test_autoregressive_grad_accum_example():
    result = _run(
        "by_feature/gradient_accumulation_for_autoregressive_models.py",
        "--steps", "2",
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert "token-weighted loss" in result.stdout


@pytest.mark.slow
def test_reference_config_training_example():
    result = _run("by_feature/reference_config_training.py", "--steps", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "zero_stage=3 -> dp_shard" in result.stdout
    assert "final loss" in result.stdout


@pytest.mark.slow
def test_packed_sft_example():
    result = _run("by_feature/packed_sft.py", "--steps", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "fill" in result.stdout and "packed training loss" in result.stdout


@pytest.mark.slow
def test_attention_bench_harness():
    """The kernel microbench must run end-to-end on CPU (interpret-mode
    flash) so the TPU window can just execute it."""
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "attention_bench.py"),
         "--seqs", "128", "--iters", "1", "--fwd_only",
         "--out", "/dev/null"],
        env=_ENV, capture_output=True, text=True, timeout=400,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    lines = [l for l in result.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 3  # flash, blockwise, xla all produced a row


def test_pod_submission_templates():
    """examples/pod/ (the reference examples/slurm analogue): YAML parses,
    scripts are bash with the launch CLI wired in."""
    import os

    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "examples", "pod")
    files = set(os.listdir(root))
    assert {"README.md", "submit_gke.yaml", "submit_xpk.sh", "submit_qr.sh"} <= files
    try:
        import yaml

        spec = yaml.safe_load(open(os.path.join(root, "submit_gke.yaml")))
        assert spec["kind"] == "JobSet"
        args = spec["spec"]["replicatedJobs"][0]["template"]["spec"]["template"][
            "spec"]["containers"][0]["args"][0]
        assert "accelerate-tpu launch" in args
    except ImportError:
        pass
    for sh in ("submit_xpk.sh", "submit_qr.sh"):
        body = open(os.path.join(root, sh)).read()
        assert body.startswith("#!/bin/bash")
        assert "accelerate-tpu launch" in body


@pytest.mark.slow
def test_big_model_inference_example():
    result = _run("big_model_inference.py", "--preset", "tiny", "--tp", "2")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "ms/token" in result.stdout
