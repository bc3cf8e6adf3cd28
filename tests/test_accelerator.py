"""End-to-end Accelerator tests: the port of the reference's canonical
``training_check`` (test_utils/scripts/test_script.py:449) — sharded training
must match single-device training exactly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.model import Model
from accelerate_tpu.parallelism_config import ParallelismConfig
from accelerate_tpu.state import GradientState
from accelerate_tpu.test_utils.training import (
    RegressionModel,
    make_regression_data,
    regression_loss,
)

LR = 0.1
ATOL = 1e-6


def _single_device_reference(data, steps_data, lr=LR, accum=1):
    """Hand-rolled single-device SGD baseline (no framework)."""
    params = {"a": jnp.float32(0.0), "b": jnp.float32(0.0)}

    def loss_fn(p, batch):
        pred = p["a"] * batch["x"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    grad_buf = None
    count = 0
    for batch in steps_data:
        g = jax.grad(loss_fn)(params, batch)
        g = jax.tree_util.tree_map(lambda t: t / accum, g)
        grad_buf = g if grad_buf is None else jax.tree_util.tree_map(jnp.add, grad_buf, g)
        count += 1
        if count % accum == 0:
            params = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, grad_buf)
            grad_buf = None
    return {k: float(v) for k, v in params.items()}


def _batches(data, bs):
    n = len(data["x"])
    return [
        {k: v[i : i + bs] for k, v in data.items()} for i in range(0, n, bs)
    ]


def make_accelerator(**kwargs):
    pcfg = kwargs.pop("parallelism_config", ParallelismConfig(dp_shard_size=8))
    return Accelerator(parallelism_config=pcfg, **kwargs)


def test_training_parity_eager_loop():
    """Reference-shaped loop (backward → clip → step → zero_grad) on an
    8-way-sharded mesh matches the single-device baseline to 1e-6."""
    accelerator = make_accelerator()
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    data = make_regression_data(64)
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = accelerator.prepare(model, optimizer)

    for epoch in range(2):
        for batch in loader:
            with accelerator.accumulate(model):
                loss = accelerator.backward(regression_loss, batch)
                optimizer.step()
                optimizer.zero_grad()

    expected = _single_device_reference(data, _batches(data, 16) * 2)
    assert abs(float(model.params["a"]) - expected["a"]) < ATOL
    assert abs(float(model.params["b"]) - expected["b"]) < ATOL
    # moving towards y=2x+3
    assert float(model.params["a"]) > 1.0


def test_training_parity_gradient_accumulation():
    """accum=2 halves update frequency; parity with baseline accumulating 2."""
    accelerator = make_accelerator(gradient_accumulation_steps=2)
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    data = make_regression_data(64)
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = accelerator.prepare(model, optimizer)

    sync_flags = []
    for batch in loader:
        with accelerator.accumulate(model):
            accelerator.backward(regression_loss, batch)
            sync_flags.append(accelerator.sync_gradients)
            optimizer.step()
            optimizer.zero_grad()

    # 4 batches, accum 2 → sync on batches 2 and 4
    assert sync_flags == [False, True, False, True]
    expected = _single_device_reference(data, _batches(data, 16), accum=2)
    assert abs(float(model.params["a"]) - expected["a"]) < ATOL
    assert abs(float(model.params["b"]) - expected["b"]) < ATOL


def test_end_of_dataloader_forces_sync():
    """Odd batch count with accum=2: the last batch syncs anyway
    (reference GradientState sync_with_dataloader)."""
    accelerator = make_accelerator(gradient_accumulation_steps=2)
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    data = make_regression_data(48)  # 3 batches of 16
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = accelerator.prepare(model, optimizer)

    sync_flags = []
    for batch in loader:
        with accelerator.accumulate(model):
            accelerator.backward(regression_loss, batch)
            sync_flags.append(accelerator.sync_gradients)
            optimizer.step()
            optimizer.zero_grad()
    assert sync_flags == [False, True, True]


def test_fused_train_step_matches_eager():
    data = make_regression_data(64)

    # eager
    acc1 = make_accelerator()
    m1 = RegressionModel()
    o1 = optax.sgd(LR)
    loader1 = acc1.prepare_data_loader(data, batch_size=16, drop_last=True)
    m1, o1 = acc1.prepare(m1, o1)
    for batch in loader1:
        with acc1.accumulate(m1):
            acc1.backward(regression_loss, batch)
            o1.step()
            o1.zero_grad()

    # fused — fresh singletons
    from accelerate_tpu.state import AcceleratorState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc2 = make_accelerator()
    m2 = RegressionModel()
    o2 = optax.sgd(LR)
    loader2 = acc2.prepare_data_loader(data, batch_size=16, drop_last=True)
    m2, o2 = acc2.prepare(m2, o2)
    step = acc2.train_step(regression_loss, model=m2, optimizer=o2)
    for batch in loader2:
        loss = step(batch)
    assert np.isfinite(float(loss))
    assert abs(float(m1.params["a"]) - float(m2.params["a"])) < ATOL
    assert abs(float(m1.params["b"]) - float(m2.params["b"])) < ATOL


def test_clip_grad_norm():
    accelerator = make_accelerator()
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    data = make_regression_data(16)
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = accelerator.prepare(model, optimizer)
    for batch in loader:
        with accelerator.accumulate(model):
            accelerator.backward(regression_loss, batch)
            norm = accelerator.clip_grad_norm_(max_norm=1e-4)
            optimizer.step()
    assert float(norm) > 0
    # grads were clipped to tiny norm → params barely moved
    assert abs(float(model.params["a"])) < 1e-3


def test_scheduler_steps_with_optimizer():
    accelerator = make_accelerator(gradient_accumulation_steps=2)
    model = RegressionModel()
    schedule = optax.linear_schedule(0.1, 0.0, 10)
    optimizer = optax.sgd(schedule)
    data = make_regression_data(64)
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer, scheduler = accelerator.prepare(model, optimizer, schedule)
    for batch in loader:
        with accelerator.accumulate(model):
            accelerator.backward(regression_loss, batch)
            optimizer.step()
            optimizer.zero_grad()
            scheduler.step()
    # 4 batches, accum 2 → 2 real optimizer steps → scheduler stepped twice
    assert scheduler.step_count == 2
    assert scheduler.get_last_lr()[0] == pytest.approx(float(schedule(2)))


def test_gather_for_metrics_drops_duplicates():
    accelerator = make_accelerator()
    data = make_regression_data(20)  # 20 % 16 = 4 → last batch padded
    loader = accelerator.prepare_data_loader(data, batch_size=16)
    seen = []
    for batch in loader:
        out = accelerator.gather_for_metrics(batch["y"])
        seen.append(np.asarray(out))
    total = np.concatenate(seen, axis=0)
    assert total.shape[0] == 20  # duplicates dropped
    np.testing.assert_allclose(total.ravel(), data["y"].ravel(), atol=1e-6)


def test_mixed_precision_bf16_forward():
    accelerator = make_accelerator(mixed_precision="bf16")
    model = RegressionModel()
    model = accelerator.prepare(model)
    out = model(np.ones((8, 1), dtype=np.float32))
    # outputs come back fp32 (policy output dtype)
    assert out.dtype == jnp.float32


def test_fp16_dynamic_scaler_runs():
    from accelerate_tpu.utils.dataclasses import GradScalerKwargs

    accelerator = make_accelerator(
        mixed_precision="fp16", kwargs_handlers=[GradScalerKwargs(init_scale=256.0)]
    )
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    data = make_regression_data(32)
    loader = accelerator.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, optimizer = accelerator.prepare(model, optimizer)
    for batch in loader:
        with accelerator.accumulate(model):
            accelerator.backward(regression_loss, batch)
            optimizer.step()
            optimizer.zero_grad()
    assert not optimizer.step_was_skipped
    assert abs(float(model.params["a"])) > 0  # learned something


def test_prepare_returns_same_order():
    accelerator = make_accelerator()
    model = RegressionModel()
    optimizer = optax.sgd(LR)
    out = accelerator.prepare(optimizer, model)
    assert isinstance(out[1], Model)
    from accelerate_tpu.optimizer import AcceleratedOptimizer

    assert isinstance(out[0], AcceleratedOptimizer)


def test_fsdp_shards_large_params():
    """Params above min_weight_size get sharded over dp_shard."""
    accelerator = make_accelerator()

    def apply_fn(params, x):
        return x @ params["w"]

    w = np.ones((256, 128), dtype=np.float32)
    model = Model(apply_fn, {"w": jnp.asarray(w)})
    model = accelerator.prepare(model)
    spec = model.shardings["w"].spec
    assert "dp_shard" in str(spec)
    # sharded dim is the largest divisible one (256)
    assert spec[0] == "dp_shard" or spec[0] == ("dp_shard",)


def test_small_params_replicated():
    accelerator = make_accelerator()
    model = RegressionModel()  # scalar params
    model = accelerator.prepare(model)
    assert model.shardings["a"].spec == ()  # replicated


def test_trigger_sync_in_backward_keeps_cadence():
    """trigger_sync_in_backward syncs exactly one extra microbatch without
    resetting the accumulation cadence (reference semantics: only the
    in-flight backward is flagged)."""
    accelerator = make_accelerator(gradient_accumulation_steps=4)

    # Inside accumulate(): the current microbatch syncs; the following
    # entries return to the unchanged cadence (sync at multiples of 4).
    flags = []
    for i in range(8):
        with accelerator.accumulate():
            if i == 1:
                accelerator.trigger_sync_in_backward()
            flags.append(accelerator.sync_gradients)
    assert flags == [False, True, False, True, False, False, False, True]

    # Outside accumulate(): the flag survives the next entry's cadence
    # recomputation, then cadence resumes where it left off.
    GradientState._reset_state()
    accelerator2 = make_accelerator(gradient_accumulation_steps=4)
    accelerator2.trigger_sync_in_backward()
    flags2 = []
    for _ in range(8):
        with accelerator2.accumulate():
            flags2.append(accelerator2.sync_gradients)
    assert flags2 == [True, False, False, True, False, False, False, True]


def test_train_step_compiles_once():
    """The fused step must hit ONE jit signature across calls: freshly
    created initial state (accum/count/scaler) carries no mesh in its
    avals while the compiled call's outputs are NamedSharded over the
    prepare-time mesh, and pjit keys its cache on exactly that — the
    regression was a whole second compile of the full fused program
    inside the first timed step (multi-second on CPU). train_step commits
    the state up front."""
    from accelerate_tpu.state import AcceleratorState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = make_accelerator()
    model = RegressionModel()
    opt = optax.sgd(LR)
    data = make_regression_data(64)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, opt = acc.prepare(model, opt)
    for flatten in (True, False):
        step = acc.train_step(
            regression_loss, model=model, optimizer=opt, flatten_params=flatten
        )
        for batch in loader:
            step(batch)
        assert step.jitted._cache_size() == 1, (
            f"flatten_params={flatten}: fused step compiled "
            f"{step.jitted._cache_size()} signatures; expected 1"
        )


def test_train_step_compiles_once_sharded():
    """Same invariant with genuinely PARTITIONED params (FSDP tiny llama —
    RegressionModel's scalar params would be fully replicated and take the
    same flat/replicated branch as the unsharded test): the initial accum
    must adopt the grad shardings up front."""
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss
    from accelerate_tpu.state import AcceleratorState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = make_accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=len(jax.devices()))
    )
    model = create_llama(LlamaConfig.tiny(), seed=0)
    model, opt = acc.prepare(model, optax.sgd(LR))
    # the partitioned-accum branch must actually be in play
    assert model.shardings is not None and not all(
        getattr(s, "is_fully_replicated", False)
        for s in jax.tree_util.tree_leaves(model.shardings)
    )
    step = acc.train_step(llama_loss, model=model, optimizer=opt)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, 256, size=(8, 16)), jnp.int32)}
    for _ in range(3):
        step(batch)
    assert step.jitted._cache_size() == 1, (
        f"fused step compiled {step.jitted._cache_size()} signatures on the "
        "sharded mesh; expected 1"
    )


def test_eager_loop_compiles_once():
    """The eager backward/step loop must also hold one jit signature per
    function across calls (same invariant as the fused step; the grad fn
    is cached by (loss_fn, model, num_steps) identity)."""
    from accelerate_tpu.state import AcceleratorState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    acc = make_accelerator()
    model = RegressionModel()
    opt = optax.sgd(LR)
    data = make_regression_data(64)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    model, opt = acc.prepare(model, opt)
    for batch in loader:
        acc.backward(regression_loss, batch)
        opt.step()
        opt.zero_grad()
    assert len(acc._grad_fns) == 1
    (grad_fn,) = acc._grad_fns.values()
    assert grad_fn._cache_size() == 1
    assert opt._update_fn._cache_size() == 1
