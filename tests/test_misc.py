"""Coverage for facade/utility surfaces not exercised elsewhere."""

import logging
import os

import numpy as np
import pytest

import jax
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.parallelism_config import ParallelismConfig
from accelerate_tpu.utils.environment import (
    clear_environment,
    patch_environment,
    str_to_bool,
)


def make_acc(**kw):
    return Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=8), **kw)


def test_profile_context_writes_trace(tmp_path):
    acc = make_acc(project_dir=str(tmp_path))
    with acc.profile():
        _ = jax.jit(lambda x: x * 2)(np.ones(8))
    prof_dir = tmp_path / "profile"
    assert prof_dir.exists()
    # xplane trace files appear under plugins/profile/...
    found = any("profile" in r for r, d, f in os.walk(prof_dir) for _ in f)
    assert found


def test_autocast_context_noop():
    acc = make_acc(mixed_precision="bf16")
    with acc.autocast():
        pass


def test_join_uneven_inputs_overrides_even_batches():
    acc = make_acc()
    data = {"x": np.arange(32.0)[:, None]}
    loader = acc.prepare_data_loader(data, batch_size=8)
    sampler = loader.batch_sampler
    if sampler is not None and hasattr(sampler, "even_batches"):
        with acc.join_uneven_inputs([None], even_batches=False):
            assert sampler.even_batches is False
        assert sampler.even_batches is True


def test_multiprocess_adapter_logging(caplog):
    from accelerate_tpu.logging import get_logger

    logger = get_logger("test_logger", log_level="INFO")
    with caplog.at_level(logging.INFO, logger="test_logger"):
        logger.info("hello")
    assert any("hello" in r.message for r in caplog.records)


def test_patch_environment():
    with patch_environment(my_test_var="42"):
        assert os.environ["MY_TEST_VAR"] == "42"
    assert "MY_TEST_VAR" not in os.environ


def test_clear_environment():
    os.environ["KEEP_ME"] = "1"
    with clear_environment():
        assert "KEEP_ME" not in os.environ
    assert os.environ["KEEP_ME"] == "1"
    del os.environ["KEEP_ME"]


def test_str_to_bool():
    assert str_to_bool("TRUE") == 1
    assert str_to_bool("0") == 0
    with pytest.raises(ValueError):
        str_to_bool("maybe")


def test_free_memory_clears_registries():
    acc = make_acc()
    from accelerate_tpu.test_utils.training import RegressionModel

    model = acc.prepare(RegressionModel())
    assert acc._models
    acc.free_memory()
    assert not acc._models


def test_local_sgd_context():
    """Construction + disabled path; real local-update/averaging semantics
    are covered in tests/test_local_sgd.py."""
    import optax

    from accelerate_tpu.local_sgd import LocalSGD
    from accelerate_tpu.test_utils.training import RegressionModel, regression_loss

    acc = make_acc()
    with LocalSGD(
        acc, RegressionModel(), optax.sgd(0.1), regression_loss,
        local_sgd_steps=2, enabled=False,
    ) as lsgd:
        for _ in range(4):
            lsgd.step()
    assert lsgd._counter == 0  # disabled: step() is a no-op


def test_gradient_accumulation_plugin_validation():
    from accelerate_tpu.utils.dataclasses import GradientAccumulationPlugin

    with pytest.raises(ValueError):
        GradientAccumulationPlugin(num_steps=0)


def test_find_executable_batch_size_backoff():
    from accelerate_tpu.utils.memory import find_executable_batch_size

    attempts = []

    @find_executable_batch_size(starting_batch_size=16)
    def run(batch_size):
        attempts.append(batch_size)
        if batch_size > 4:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return batch_size

    assert run() == 4
    assert attempts == [16, 8, 4]


def test_mixed_precision_policy_casts():
    import jax.numpy as jnp

    from accelerate_tpu.utils.dataclasses import MixedPrecisionPolicy

    policy = MixedPrecisionPolicy.from_mixed_precision("bf16")
    tree = {"w": jnp.ones(2, jnp.float32), "i": jnp.ones(2, jnp.int32)}
    out = policy.cast_to_compute(tree)
    assert out["w"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32
    back = policy.cast_to_output(out)
    assert back["w"].dtype == jnp.float32


def test_kwargs_handler_to_kwargs():
    from accelerate_tpu.utils.dataclasses import GradScalerKwargs

    kw = GradScalerKwargs(init_scale=128.0)
    assert kw.to_kwargs() == {"init_scale": 128.0}


def test_ddp_comm_hook_bf16_grads():
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.utils.dataclasses import DistributedDataParallelKwargs
    from accelerate_tpu.test_utils.training import RegressionModel, make_regression_data, regression_loss

    acc = make_acc(kwargs_handlers=[DistributedDataParallelKwargs(comm_hook="bf16")])
    model = RegressionModel()
    model, opt = acc.prepare(model, optax.sgd(0.1))
    data = make_regression_data(16)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    for batch in loader:
        with acc.accumulate(model):
            acc.backward(regression_loss, batch)
            assert opt.grads["a"].dtype == jnp.bfloat16  # compressed
            opt.step()
            opt.zero_grad()
    assert abs(float(model.params["a"])) > 0


def test_save_load_state_hooks(tmp_path):
    import optax

    from accelerate_tpu.test_utils.training import RegressionModel

    acc = make_acc(project_dir=str(tmp_path))
    calls = []
    acc.register_save_state_pre_hook(lambda models, w, d: calls.append(("save", d)))
    acc.register_load_state_pre_hook(lambda models, d: calls.append(("load", d)))
    model, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
    acc.save_state(str(tmp_path / "ckpt"))
    acc.load_state(str(tmp_path / "ckpt"))
    assert [c[0] for c in calls] == ["save", "load"]


def test_ddp_comm_hook_fused_path():
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.utils.dataclasses import DistributedDataParallelKwargs
    from accelerate_tpu.test_utils.training import RegressionModel, make_regression_data, regression_loss

    acc = make_acc(kwargs_handlers=[DistributedDataParallelKwargs(comm_hook="bf16")])
    model, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
    step = acc.train_step(regression_loss)
    data = make_regression_data(16)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    for batch in loader:
        loss = step(batch)
    import numpy as np

    assert np.isfinite(float(loss))
    assert abs(float(model.params["a"])) > 0


def test_hooks_receive_resolved_dir(tmp_path):
    import optax

    from accelerate_tpu.utils.dataclasses import ProjectConfiguration
    from accelerate_tpu.test_utils.training import RegressionModel
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    from accelerate_tpu import Accelerator
    from accelerate_tpu.parallelism_config import ParallelismConfig

    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        project_config=ProjectConfiguration(
            project_dir=str(tmp_path), automatic_checkpoint_naming=True
        ),
    )
    seen = []
    acc.register_save_state_pre_hook(lambda m, w, d: seen.append(d))
    acc.register_load_state_pre_hook(lambda m, d: seen.append(d))
    model, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
    acc.save_state()  # no explicit dir
    acc.load_state()
    assert seen[0] is not None and "checkpoint_0" in seen[0]
    assert seen[1] is not None and "checkpoint_0" in seen[1]


def test_comm_wrapper_rejected():
    from accelerate_tpu.utils.dataclasses import DistributedDataParallelKwargs

    with pytest.raises(ValueError, match="comm_wrapper"):
        DistributedDataParallelKwargs(comm_wrapper="power_sgd")


def test_eval_step():
    import optax

    from accelerate_tpu.test_utils.training import RegressionModel, make_regression_data

    acc = make_acc()
    model = acc.prepare(RegressionModel(a=2.0, b=3.0))
    ev = acc.eval_step(lambda m, batch: m(batch["x"]))
    data = make_regression_data(16)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    for batch in loader:
        preds = ev(batch)
    import numpy as np

    np.testing.assert_allclose(
        np.asarray(preds).ravel(), np.asarray(batch["y"]).ravel() if hasattr(batch["y"], "ravel") else np.asarray(batch["y"]), atol=1e-5
    )


def test_no_sync_context_blocks_step():
    import optax

    from accelerate_tpu.test_utils.training import RegressionModel, make_regression_data, regression_loss

    acc = make_acc()
    model, opt = acc.prepare(RegressionModel(), optax.sgd(0.1))
    data = make_regression_data(16)
    loader = acc.prepare_data_loader(data, batch_size=16, drop_last=True)
    (batch,) = list(loader)
    with acc.no_sync(model):
        acc.backward(regression_loss, batch)
        opt.step()
    assert opt.step_was_skipped
    assert float(model.params["a"]) == 0.0
    # outside no_sync the same grads apply
    acc.gradient_state._set_sync_gradients(True)
    opt.step()
    assert not opt.step_was_skipped
    assert float(model.params["a"]) != 0.0


def test_multiple_models_checkpoint_suffixes(tmp_path):
    import os

    import optax

    from accelerate_tpu.test_utils.training import RegressionModel

    acc = make_acc(project_dir=str(tmp_path))
    m1 = acc.prepare(RegressionModel(a=1.0))
    m2 = acc.prepare(RegressionModel(a=2.0))
    o1 = acc.prepare_optimizer(optax.sgd(0.1))
    ckpt = acc.save_state(str(tmp_path / "ckpt"))
    assert os.path.isdir(os.path.join(ckpt, "model"))
    assert os.path.isdir(os.path.join(ckpt, "model_1"))
    import jax.numpy as jnp

    m1.params = {"a": jnp.float32(0.0), "b": jnp.float32(0.0)}
    m2.params = {"a": jnp.float32(0.0), "b": jnp.float32(0.0)}
    acc.load_state(str(tmp_path / "ckpt"))
    assert float(m1.params["a"]) == 1.0
    assert float(m2.params["a"]) == 2.0


def test_fsdp_plugin_wiring():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.model import Model
    from accelerate_tpu.utils.dataclasses import FSDPPlugin

    # min_weight_size raised → medium param stays replicated
    acc = make_acc(fsdp_plugin=FSDPPlugin(min_weight_size=2**20))
    model = Model(lambda p, x: x @ p["w"], {"w": jnp.ones((256, 128))})
    model = acc.prepare(model)
    assert model.shardings["w"].spec == P()

    # custom rule wins
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state(); GradientState._reset_state(); PartialState._reset_state()
    acc2 = make_acc(
        fsdp_plugin=FSDPPlugin(sharding_rules=[(r"^w$", P(None, "dp_shard"))])
    )
    model2 = Model(lambda p, x: x @ p["w"], {"w": jnp.ones((256, 128))})
    model2 = acc2.prepare(model2)
    assert model2.shardings["w"].spec == P(None, "dp_shard")


@pytest.mark.parametrize("policy", ["nothing", "auto"])
def test_fsdp_plugin_activation_checkpointing(policy, monkeypatch):
    """The plugin turns the default (and "nothing") into "minimal", which then
    counts as set: the train step's plan leaves it alone."""
    import optax

    import accelerate_tpu.accelerator as accelerator_module
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss
    from accelerate_tpu.utils.dataclasses import FSDPPlugin

    acc = make_acc(fsdp_plugin=FSDPPlugin(activation_checkpointing=True))
    cfg = LlamaConfig.tiny(remat_policy=policy)
    model = create_llama(cfg)
    model, _ = acc.prepare(model, optax.sgd(0.1))
    assert model.config.remat_policy == "minimal"
    monkeypatch.setattr(accelerator_module, "_device_memory", lambda: (1 << 40, 0))
    step = acc.train_step(llama_loss)
    step({"input_ids": jax.numpy.zeros((8, 16), jax.numpy.int32)})
    assert step.plan["remat"] == "minimal" and step.plan["rungs_tried"] == 0
    assert model.config.remat_policy == "minimal"


def test_tpu_configured_probe(monkeypatch):
    """ADVICE r4: _tpu_configured must detect a bare TPU-VM host (TPU device
    nodes present, no TPU env vars) and must honor an explicit non-TPU
    JAX_PLATFORMS as the fork opt-out."""
    import glob

    from accelerate_tpu import launchers

    for var in ("JAX_PLATFORMS", "TPU_NAME"):
        monkeypatch.delenv(var, raising=False)
    # bare TPU-VM host: device nodes present, no env vars
    monkeypatch.setattr(
        glob, "glob", lambda pat: ["/dev/accel0"] if "accel" in pat else []
    )
    assert launchers._tpu_configured() is True
    # explicit cpu platforms wins over hardware presence
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launchers._tpu_configured() is False
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert launchers._tpu_configured() is True
    # CPU-only host with libtpu pip-installed: NOT TPU-configured
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(glob, "glob", lambda pat: [])
    assert launchers._tpu_configured() is False


def test_model_scoped_fsdp_hints():
    """ADVICE r4: gather pins read the hints of the model whose apply is
    running, not whichever model was prepared last."""
    import jax
    from jax.sharding import Mesh

    from accelerate_tpu.parallel import sharding as sh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp_shard",))
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._shared_state["fsdp_axes"] = ("dp_shard",)
    AcceleratorState._shared_state["fsdp_min_weight_size"] = 2**10
    try:
        # global fallback
        assert sh._fsdp_use_hints(mesh) == (("dp_shard",), 2**10)
        # scoped hints win while the model apply is in flight
        with sh.model_fsdp_hints(((), 2**20)):
            assert sh._fsdp_use_hints(mesh) == ((), 2**20)
        # and restore on exit
        assert sh._fsdp_use_hints(mesh) == (("dp_shard",), 2**10)
    finally:
        AcceleratorState._shared_state.pop("fsdp_axes", None)
        AcceleratorState._shared_state.pop("fsdp_min_weight_size", None)


def test_ulysses_custom_inner_window_signature():
    """ADVICE r4: a custom inner that cannot accept `window` fails with a
    clear TypeError at construction, not a confusing one at trace time."""
    import jax
    from jax.sharding import Mesh

    from accelerate_tpu.ops.ulysses import make_ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sp",))

    def windowless_inner(q, k, v, causal=True, segment_ids=None):
        return q

    with pytest.raises(TypeError, match="window"):
        make_ulysses_attention(mesh, inner=windowless_inner, window=64)

    def windowed_inner(q, k, v, causal=True, segment_ids=None, window=None):
        return q

    make_ulysses_attention(mesh, inner=windowed_inner, window=64)


def test_relative_leaf_gate():
    """bench.relative_leaf_gate — the shared numerics gate for the bench
    flash check and benchmarks/kernel_validation.py. Window-1 motivation:
    a bf16-round-off dv (one ulp over an absolute atol) must PASS while a
    real lowering bug (O(1) error) must FAIL."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    ref = [np.linspace(-1, 1, 64).reshape(8, 8)]
    base = [ref[0] + 0.03]  # bf16 baseline round-off
    ok, details = bench.relative_leaf_gate([ref[0] + 0.05], base, ref, ("dv",))
    assert ok and details["dv"]["pass"]  # 1.7x baseline error: bf16 noise

    ok, details = bench.relative_leaf_gate([ref[0] + 1.0], base, ref, ("dv",))
    assert not ok  # O(1) error: a real lowering bug must fail

    # near-zero baseline error: the absolute floor keeps exact-match
    # kernels passing
    ok, _ = bench.relative_leaf_gate([ref[0]], [ref[0]], ref, ("out",))
    assert ok
