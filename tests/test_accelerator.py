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


# ------------------------------------------ the fused step's memory (PR 36)
def _tree_bytes(tree):
    return sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _tiny_llama_step(accelerator=None, remat_policy=None, optimizer=None, **step_kw):
    """(model, optimizer, step, batch) of a tiny float32 llama under
    ``Accelerator()`` (parameters replicated, so the flat form is allowed)."""
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss

    accelerator = accelerator or Accelerator()
    kw = {} if remat_policy is None else {"remat_policy": remat_policy}
    model = create_llama(LlamaConfig.tiny(compute_dtype=jnp.float32, **kw), seed=0)
    model, opt = accelerator.prepare(model, optimizer or optax.adamw(1e-2))
    step = accelerator.train_step(llama_loss, max_grad_norm=1.0, **step_kw)
    ids = np.random.default_rng(0).integers(0, 256, size=(10, 8, 16)).astype(np.int32)
    return model, opt, step, ids


_FORMS = {"tree": {}, "flat": {"flatten_params": True}, "multi": {"multi_step": True}}


@pytest.mark.parametrize("form", sorted(_FORMS))
def test_train_step_carries_no_accumulator_at_one_microbatch(form):
    """At one micro-batch an update the state holds no accumulator: the
    program's arguments are parameters + optimizer state + batch (and the
    12 bytes of count and scaler), its results the same with the loss."""
    model, opt, step, ids = _tiny_llama_step(**_FORMS[form])
    batch = {"input_ids": jnp.asarray(ids[:1] if form == "multi" else ids[0])}
    lowered = step.lower(batch)
    args = lowered.args_info[0]
    assert jax.tree_util.tree_leaves(args[2]) == []  # the accumulator's place
    state_bytes = _tree_bytes(model.params) + _tree_bytes(opt.opt_state)
    assert _tree_bytes(args) == state_bytes + _tree_bytes(batch) + 12
    assert _tree_bytes(lowered.out_info) == state_bytes + 12 + 4
    step(batch)
    assert step.plan["accumulator_bytes"] == 0


def test_train_step_keeps_its_accumulator_under_accumulation():
    model, opt, step, ids = _tiny_llama_step(
        accelerator=Accelerator(gradient_accumulation_steps=4)
    )
    batch = {"input_ids": jnp.asarray(ids[0])}
    args = step.lower(batch).args_info[0]
    params_bytes = _tree_bytes(model.params)
    assert _tree_bytes(args[2]) == params_bytes == step.plan["accumulator_bytes"]
    assert _tree_bytes(args) == (
        2 * params_bytes + _tree_bytes(opt.opt_state) + _tree_bytes(batch) + 12
    )


@pytest.mark.parametrize("form", sorted(_FORMS))
def test_ten_steps_match_optax_by_hand(form):
    """Ten steps at one micro-batch an update give the losses and the
    parameters of the same ten steps through optax by hand."""
    from accelerate_tpu.models.llama import llama_loss

    tx = optax.adamw(1e-2)
    model, opt, step, ids = _tiny_llama_step(optimizer=tx, **_FORMS[form])
    params = jax.tree_util.tree_map(jnp.copy, model.params)
    opt_state = tx.init(params)

    @jax.jit
    def by_hand(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: llama_loss(model.bind(p), batch))(params)
        factor = jnp.minimum(1.0, 1.0 / (optax.global_norm(grads) + 1e-6))
        grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    expected = []
    for i in range(10):
        params, opt_state, loss = by_hand(params, opt_state, {"input_ids": jnp.asarray(ids[i])})
        expected.append(float(loss))
    if form == "multi":
        losses = [float(x) for x in step({"input_ids": jnp.asarray(ids)})]
    else:
        losses = [float(step({"input_ids": jnp.asarray(ids[i])})) for i in range(10)]
    np.testing.assert_allclose(losses, expected, rtol=2e-5)
    assert expected[-1] < expected[0] - 0.1  # the ten steps trained
    for got, want in zip(jax.tree_util.tree_leaves(model.params),
                         jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_fused_step_accumulates_updates_on_the_fourth_call_and_zeroes():
    data = make_regression_data(64)
    accelerator = make_accelerator(gradient_accumulation_steps=4)
    model, opt = accelerator.prepare(RegressionModel(), optax.sgd(LR))
    step = accelerator.train_step(regression_loss, model=model, optimizer=opt)
    batches = _batches(data, 8)
    seen = []
    for batch in batches:
        step({k: jnp.asarray(v) for k, v in batch.items()})
        seen.append(float(model.params["a"]))
    assert seen[0] == seen[1] == seen[2] == 0.0 and seen[3] != 0.0
    assert seen[3] == seen[4] == seen[5] == seen[6] != seen[7]
    # the second update equals the reference's only if the first zeroed the sum
    expected = _single_device_reference(data, batches, accum=4)
    assert abs(seen[7] - expected["a"]) < ATOL
    assert abs(float(model.params["b"]) - expected["b"]) < ATOL


# ------------------------------------------------ the step's plan (PR 36)
def _fused_compiles(caplog):
    """How often XLA has compiled the fused step, by jax's own log
    (``jax.log_compiles``), which names the program."""
    return sum("Finished XLA compilation of jit(fused)" in r.getMessage() for r in caplog.records)


def _rung_needs(ids):
    """What each rung's compiled step needs here: temporaries + arguments."""
    from accelerate_tpu.analysis.lowering import memory_table
    from accelerate_tpu.models.llama import REMAT_LADDER
    from accelerate_tpu.state import AcceleratorState, PartialState

    needs = {}
    for rung in REMAT_LADDER:
        _, _, step, _ = _tiny_llama_step(remat_policy=rung)
        table = memory_table(step.lower({"input_ids": jnp.asarray(ids[0])}).compile())
        needs[rung] = table["hbm_live"]
        for state in (AcceleratorState, GradientState, PartialState):
            state._reset_state()
    return needs


@pytest.mark.parametrize("case", ["all_fit", "first_does_not_fit", "none_fits", "no_limit"])
def test_plan_keeps_the_lightest_rung_that_fits(case, monkeypatch, caplog):
    import accelerate_tpu.accelerator as accelerator_module
    from accelerate_tpu.accelerator import _PLAN_MARGIN
    from accelerate_tpu.models.llama import REMAT_LADDER

    ids = np.random.default_rng(0).integers(0, 256, size=(10, 8, 16)).astype(np.int32)
    needs = _rung_needs(ids)
    first = REMAT_LADDER[0]
    assert needs[first] > needs["nothing"]
    room = 1 - _PLAN_MARGIN
    memory = {
        "all_fit": (int(max(needs.values()) / room) + 1, 0),
        "first_does_not_fit": (int(needs[first] / room) - 1, 0),
        "none_fits": (1, 0),
        "no_limit": None,
    }[case]
    fitting = [r for r in REMAT_LADDER if memory and needs[r] <= memory[0] * room]
    expected = (fitting or ["nothing"])[0]
    monkeypatch.setattr(accelerator_module, "_device_memory", lambda: memory)

    batch = {"input_ids": jnp.asarray(ids[0])}
    # what a step whose policy the user set compiles: it makes no plan
    _, _, fixed, _ = _tiny_llama_step(remat_policy=expected)
    with jax.log_compiles():
        for _ in range(3):
            fixed(batch)
    assert fixed.plan["rungs_tried"] == 0 and _fused_compiles(caplog) == 1
    caplog.clear()
    for state in (accelerator_module.AcceleratorState, GradientState):
        state._reset_state()

    model, opt, step, _ = _tiny_llama_step()
    assert model.config.remat_policy == "auto"
    assert step.plan["remat"] == "nothing" and step.plan["rungs_tried"] == 0
    with jax.log_compiles():
        for _ in range(3):
            step(batch)
    compiles = _fused_compiles(caplog)
    assert step.plan["remat"] == expected
    assert model.config.remat_policy == "auto"  # the configuration is the user's
    assert step.jitted._cache_size() == 1
    if case == "no_limit":
        assert step.plan["rungs_tried"] == 0 and step.plan["hbm_live"] is None
    else:
        tried = REMAT_LADDER.index(expected) + 1
        assert step.plan["rungs_tried"] == tried
        assert step.plan["hbm_live"] == needs[expected], (needs, step.plan, fixed.plan)
        assert step.plan["bytes_limit"] == memory[0]
        # a rung is one compile, and the kept one runs the steps: where the
        # first fits, as many programs as without a plan
        assert compiles == tried
    if case == "first_does_not_fit":
        assert expected != first and step.plan["rungs_tried"] >= 2
    if case == "no_limit":
        assert compiles == 1


@pytest.mark.parametrize("policy", ["nothing", "minimal", "dots", "full"])
def test_plan_leaves_a_policy_the_user_set(policy, monkeypatch):
    import accelerate_tpu.accelerator as accelerator_module

    monkeypatch.setattr(accelerator_module, "_device_memory", lambda: (1 << 40, 0))
    model, _, step, ids = _tiny_llama_step(remat_policy=policy)
    step({"input_ids": jnp.asarray(ids[0])})
    assert model.config.remat_policy == policy
    assert step.plan["remat"] == policy and step.plan["rungs_tried"] == 0
    assert step.plan["bytes_limit"] is None


def _tiny_family(family, policy):
    if family == "llama":
        from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss

        model = create_llama(
            LlamaConfig.tiny(compute_dtype=jnp.float32, remat_policy=policy), seed=0)
        return model, llama_loss
    from accelerate_tpu.models.gpt2 import GPT2Config, create_gpt2, gpt2_loss

    return create_gpt2(GPT2Config.tiny(compute_dtype=jnp.float32, remat_policy=policy)), gpt2_loss


def _loss_and_grads(family, policy):
    model, loss_fn = _tiny_family(family, policy)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, size=(4, 16)), jnp.int32)
    fn = jax.value_and_grad(lambda p: loss_fn(model.bind(p), {"input_ids": ids}))
    return fn, model.params


@pytest.mark.parametrize("policy", ["full", "dots", "dots_no_batch", "minimal", "auto"])
@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_every_rung_gives_the_same_loss_and_gradients(family, policy):
    """A saved activation and a recomputed one are the same values."""
    fn, params = _loss_and_grads(family, policy)
    base_fn, _ = _loss_and_grads(family, "nothing")
    loss, grads = jax.jit(fn)(params)
    base_loss, base_grads = jax.jit(base_fn)(params)
    np.testing.assert_allclose(float(loss), float(base_loss), rtol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(base_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_auto_is_nothing_outside_a_train_step(family):
    """A model applied by hand traces "auto" as "nothing"; inside
    ``auto_remat(rung)`` (the train step's plan) as that rung."""
    from accelerate_tpu.models.llama import auto_remat

    def text(policy):
        fn, params = _loss_and_grads(family, policy)
        return str(jax.make_jaxpr(fn)(params))

    assert text("auto") == text("nothing") != text("full")
    with auto_remat("full"):
        assert text("auto") == text("full")
        assert text("nothing") != text("full")  # a policy that was set stands
    with pytest.raises(ValueError, match="remat_policy"):
        text("everything")


def _count_kernels(jaxpr, name):
    """Calls of the Pallas kernel ``name`` in a jaxpr, loops' bodies once."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            found += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _count_kernels(sub, name)
    return found


@pytest.mark.parametrize("policy, forwards", [
    ("nothing", 2), ("minimal", 2), ("dots_no_batch", 2), ("dots", 1), ("full", 1),
])
def test_dots_saves_what_the_flash_kernel_hands_its_backward(policy, forwards):
    """Under "dots" a layer's backward finds the forward kernel's output and
    logsumexp saved by name and does not run the kernel again."""
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss

    model = create_llama(LlamaConfig.tiny(
        compute_dtype=jnp.float32, remat_policy=policy, attention_impl="flash",
        max_position_embeddings=128), seed=0)
    ids = jnp.zeros((2, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: llama_loss(model.bind(p), {"input_ids": ids})))(model.params).jaxpr
    assert _count_kernels(jaxpr, "flash_fwd") == forwards  # the layers are one scan
    assert _count_kernels(jaxpr, "flash_bwd_dq") == 1
