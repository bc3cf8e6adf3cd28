"""Each driver end to end at a tiny preset, past the harness's look for a chip;
then the same with the timed path broken underneath, and the control in the
program's place: ``correct`` has to come out false."""

import numpy as np
import pytest

from chipbench import faults, lib, run
from conftest import args


def test_train_cell_end_to_end(tiny_cells):
    out = run.execute(args("tiny-llama.train"))
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert out["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "check" and set(out["check"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_serve_cell_end_to_end(tiny_cells):
    out = run.execute(args("tiny-gpt2.serve", seed=2**31 + 3))
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 4
    assert set(out["metrics"]) == {"serve_tokens_per_s", "norm_latency_p50_ms", "setup_s"}
    assert out["check"]["wrong_answers"] == [0.0, 0.0]
    assert set(out["check"]) == {"logit_gap", "sample_gap", "wrong_answers"}


def test_a_run_needs_the_chip():
    with pytest.raises(lib.BenchError, match="needs a TPU"):
        run.execute(args("mistral-7b-2l.train-seq2048"))


def test_a_cell_whose_limits_miss_a_number_is_refused():
    with pytest.raises(lib.BenchError):
        run.judge({"loss_gap": 0.1}, {"loss_gap": 1.0, "grad_gap": 1.0})
    assert run.judge({"a": float("nan")}, {"a": 1.0})[0] is False


# ------------------------------------------------------------------ planted faults
def test_train_step_that_leaves_its_state_unchanged_is_not_correct(tiny_cells, monkeypatch):
    import optax

    # Adam's state and no update: the step returns the parameters as they were
    monkeypatch.setattr(
        optax, "adamw", lambda lr, weight_decay=0.0: optax.chain(optax.scale_by_adam(), optax.scale(0.0))
    )
    out = run.execute(args("tiny-llama.train"))
    assert not out["correct"]
    assert out["check"]["change_gap"][0] == pytest.approx(1.0, abs=1e-3)


def test_train_step_that_leaves_out_half_of_the_batch_is_not_correct(tiny_cells, monkeypatch):
    import jax.numpy as jnp

    from accelerate_tpu.models import llama

    whole = llama.llama_loss

    def half(model_view, batch, **kw):
        keep = jnp.arange(batch["input_ids"].shape[1] - 1) < (batch["input_ids"].shape[1] - 1) // 2
        mask = jnp.broadcast_to(keep, batch["input_ids"][:, 1:].shape)
        return whole(model_view, {**batch, "loss_mask": mask}, **kw)

    monkeypatch.setattr(llama, "llama_loss", half)
    out = run.execute(args("tiny-llama.train"))
    assert not out["correct"], out["check"]


def test_served_token_altered_where_it_is_produced_is_not_correct(tiny_cells):
    with faults.planted("token_altered"):
        out = run.execute(args("tiny-gpt2.serve", seed=11))
    assert not out["correct"], out["check"]
    assert out["check"]["logit_gap"][0] > out["check"]["logit_gap"][1]


def test_sampler_that_ignores_top_k_is_not_correct(tiny_cells):
    with faults.planted("top_k_ignored"):
        out = run.execute(args("tiny-gpt2.serve", seed=13))
    assert not out["correct"], out["check"]
    assert out["check"]["sample_gap"][0] > out["check"]["sample_gap"][1]
    assert out["check"]["logit_gap"][0] <= out["check"]["logit_gap"][1]


def test_served_prompt_that_comes_back_altered_is_a_wrong_answer(tiny_cells):
    import types

    from chipbench.drivers import serve

    def record(prompt, tokens):
        r = serve.Record(types.SimpleNamespace(prompt=np.array(prompt), max_new_tokens=1), 0, 0.0)
        r.result = types.SimpleNamespace(tokens=np.array(tokens))
        return r

    ctx = types.SimpleNamespace(config={"vocab_size": 256})
    good, bad, wild = record([1, 2], [1, 2, 3]), record([1, 2], [1, 9, 3]), record([1, 2], [1, 2, 300])
    assert serve.wrong_answers(ctx, [good, bad, wild]) == 2


# --------------------------------------------------------------------- the control
def test_train_control_one_precision_lower_is_not_correct(tiny_cells):
    """The reference in float8 in the program's place fails a limit of the tiny
    cell, and so does the reference with half of the batch left out."""
    workload, config = lib.load_cell("tiny-llama.train")
    driver = lib.load_module("drivers", "train")
    ctx = run.Context("tiny-llama.train", workload, config, 5, 1)
    state = driver.setup(ctx)
    driver.release(state)
    readings = driver.control_readings(ctx, state)
    assert not run.judge(readings["control_float8"], workload["limits"])[0]
    assert not run.judge(readings["fault_half_batch"], workload["limits"])[0]


def test_serve_control_one_precision_lower_reads_a_wider_gap(tiny_cells):
    workload, config = lib.load_cell("tiny-gpt2.serve")
    driver = lib.load_module("drivers", "serve")
    ctx = run.Context("tiny-gpt2.serve", workload, config, 5, 1)
    state = driver.setup(ctx)
    driver.window(ctx, state, 1.0, run.WindowHooks(lib.CompileWatch.get()))
    driver.release(state)
    numbers = driver.check(ctx, state)
    control = driver.control_readings(ctx, state)["control_float8"]
    assert run.judge(numbers, workload["limits"])[0], numbers
    assert not run.judge(control, workload["limits"])[0], control
    assert control["logit_gap"] > numbers["logit_gap"]
