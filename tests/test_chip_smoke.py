"""Rehearsals of ``chip_smoke.py`` on the CPU (``on-chip-measurement`` guide,
section 2): its phase functions at tiny sizes, with Pallas kernels in interpret
mode and four virtual devices standing in for four chips. The script itself has
no size or platform option: these tests steer it. What only the chip can show
(a Pallas custom call in the compiled text, peak device memory, the answers at
real widths) is checked by ``main()`` there and only counted here.

Also here: the measurement paths refuse to run without a chip.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

import jax

from accelerate_tpu.models.gpt2 import GPT2Config
from accelerate_tpu.models.llama import LlamaConfig
from accelerate_tpu.utils.dataclasses import ServingConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_ROOT, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses resolves annotations through it
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke")


@pytest.fixture
def fresh_state(smoke):
    """A phase that raises half-way leaves its accelerator's shared state."""
    yield
    smoke._reset_accelerator_state()


def _one_json_line_per_phase(capsys):
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert all("phase" in line for line in lines)
    return [line["phase"] for line in lines]


def test_train_phase_on_one_of_several_devices(smoke, fresh_state, capsys):
    facts = smoke.train_phase(
        LlamaConfig.tiny(attention_impl="flash"), batch_size=2, seq_len=32, steps=6,
        devices=jax.devices()[:1], reference_attention="blockwise",
    )
    assert _one_json_line_per_phase(capsys) == ["train"]
    assert facts["mesh_devices"] == [jax.devices()[0].id] and facts["mesh"] == {}
    assert facts["step_compiles"] == 1 and len(facts["losses"]) == 6
    assert set(facts["eval_loss"]) == {"flash", "blockwise"}
    # interpret mode leaves no custom call: only main(), on the chip, asks for one
    assert facts["pallas_custom_calls"] == 0
    assert facts["native_packing"] in ("csrc", "numpy")


def test_train_phase_fails_on_a_loss_that_does_not_fall(smoke, fresh_state, monkeypatch):
    monkeypatch.setattr(smoke, "LEARNING_RATE", 0.0)
    with pytest.raises(smoke.SmokeFailure, match="does not fall"):
        smoke.train_phase(
            LlamaConfig.tiny(attention_impl="flash"), batch_size=2, seq_len=32, steps=4,
            devices=jax.devices()[:1],
        )


def test_sharded_phase_on_four_virtual_devices(smoke, fresh_state, capsys, monkeypatch):
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    facts = smoke.sharded_phase(
        LlamaConfig.tiny(attention_impl="flash"),
        LlamaConfig.tiny(attention_impl="flash", num_hidden_layers=4),
        batch_size=4, seq_len=32, steps=4, deep_steps=3,
    )
    assert _one_json_line_per_phase(capsys) == [
        "train_1dev", "train_fsdp4", "train_fsdp2_tp2", "train_deep_fsdp4", "sharded",
    ]
    assert facts["devices"] == 4 and facts["deep_layers"] == 4
    assert max(facts["max_loss_deviation_from_one_device"].values()) <= facts["tolerance"]


def test_spread_tells_sharded_from_replicated(smoke):
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(jax.devices()[:4], ("x",))
    leaf = jnp.zeros((4096, 4), jnp.float32)
    sharded = smoke._spread({"w": jax.device_put(leaf, NamedSharding(mesh, P("x")))}, 4)
    assert sharded["fewest_devices_for_a_large_leaf"] == 4
    assert sharded["bytes_per_device"] == [leaf.nbytes // 4] * 4
    replicated = smoke._spread({"w": jax.device_put(leaf, NamedSharding(mesh, P()))}, 4)
    assert replicated["fewest_devices_for_a_large_leaf"] == 1
    assert replicated["bytes_per_device"] == [leaf.nbytes]


def test_kernel_parity_phase_at_tiny_widths(smoke, capsys):
    facts = smoke.kernel_parity_phase(
        {"mha": (4, 4, 8), "gqa": (8, 2, 16)}, vocab_sizes=(64, 200),
        block_size=4, slots=3, blocks_per_row=4, window=3,
    )
    assert _one_json_line_per_phase(capsys) == ["kernels"]
    assert len(facts["max_error"]) == 8  # decode and verify, two widths, two pools
    assert max(facts["max_error"].values()) <= facts["tolerance"]
    assert all(rows["kernel"] == rows["reference"] for rows in facts["sampled_tokens"].values())


def test_serve_phase_with_the_paged_kernels(smoke, capsys):
    requests = [
        smoke.Request(prompt_len=5, max_new_tokens=6),
        smoke.Request(prompt_len=17, max_new_tokens=4, temperature=0.8, top_k=5, seed=1),
        smoke.Request(prompt_len=30, max_new_tokens=8, temperature=1.0, top_p=0.9, seed=2),
    ]
    facts = smoke.serve_phase(
        GPT2Config.tiny(),
        ServingConfig(
            mode="continuous", kv_cache="paged", attention_impl="pallas",
            engine_slots=4, engine_max_len=64,
        ),
        requests,
    )
    assert _one_json_line_per_phase(capsys) == ["serve"]
    assert facts["attention_impl"] == "pallas"
    assert facts["tokens_returned"] == [11, 21, 38]
    assert facts["programs"] == {"prefill_insert": 1, "decode_step": 1}
    assert facts["tokens_equal_to_reference_engine"]["of"] == 18


def test_serve_phase_fails_when_the_engine_gives_the_kernel_up(smoke, monkeypatch):
    """A sliding-window config makes the engine fall back to the reference op
    with a warning; the smoke turns that into a failure."""
    import accelerate_tpu.models.gpt2 as gpt2
    from accelerate_tpu.models.llama import create_llama

    # serve_phase builds a GPT-2; hand it the windowed llama instead
    monkeypatch.setattr(gpt2, "create_gpt2", lambda config, seed=0: create_llama(config, seed=seed))
    serving = ServingConfig(
        mode="continuous", kv_cache="paged", attention_impl="pallas",
        engine_slots=2, engine_max_len=32,
    )
    with pytest.warns(UserWarning, match="sliding-window"):
        with pytest.raises(smoke.SmokeFailure, match="the engine runs 'reference'"):
            smoke.serve_phase(
                LlamaConfig.tiny(sliding_window=16), serving, [smoke.Request(4, 3)]
            )


# ------------------------------------------------------- no chip, no result
def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, script), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=_ROOT,
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")], ids=["one_chip", "four_chips"])
def test_chip_smoke_refuses_the_cpu(args):
    proc = _run("chip_smoke.py", *args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_has_no_interpret_mode_and_no_size_option(smoke):
    with open(os.path.join(_ROOT, "chip_smoke.py")) as f:
        source = f.read()
    assert "interpret=True" not in source
    with pytest.raises(SystemExit):
        smoke.main(["--layers", "1"])
    with pytest.raises(SystemExit):
        smoke.main(["--chips", "2"])


@pytest.mark.parametrize("args", [("--child",), ()], ids=["child", "parent"])
def test_bench_gives_no_number_without_a_chip(args):
    proc = _run("bench.py", *args)
    assert proc.returncode != 0
    assert "metric" not in proc.stdout and proc.stdout.strip() == ""
    assert "measures a TPU" in proc.stderr


def test_detect_peak_flops_raises_on_an_unknown_kind():
    bench = _load("bench")
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench.detect_peak_flops(v5e) == 197e12
    for device in (
        types.SimpleNamespace(platform="tpu", device_kind="TPU v9 mega"),
        types.SimpleNamespace(platform="cpu", device_kind="cpu"),
        jax.devices()[0],
    ):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            bench.detect_peak_flops(device)
