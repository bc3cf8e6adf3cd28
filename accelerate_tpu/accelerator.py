"""The Accelerator facade.

TPU-native re-design of the reference's ``accelerator.py`` (4,359 LoC,
/root/reference/src/accelerate/accelerator.py). Same capability surface —
``prepare``, ``backward``, ``accumulate``, ``clip_grad_norm_``,
``gather_for_metrics``, ``save_state``/``load_state``, trackers, ``autocast``,
``profile`` — over a fundamentally different execution model:

* ``prepare()`` computes GSPMD shardings for params/optimizer-state from
  ``ParallelismConfig`` (one mesh; DP/FSDP/HSDP/TP/CP/SP are sharding rules,
  not engine integrations — SURVEY §7 design stance);
* the training loop can stay reference-shaped (``backward``→``step``→
  ``zero_grad``; each piece is an independently jitted function), or use
  :meth:`train_step` to fuse forward/backward/accumulate/update into ONE
  compiled program — the high-MFU path;
* there is no wrapping/monkey-patching: params and optimizer state are
  functional pytrees; "in-place" user semantics are preserved by writing the
  new pytrees back onto the ``Model``/``AcceleratedOptimizer`` objects.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Any, Callable, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import tracing
from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .model import Model
from .optimizer import AcceleratedOptimizer, DynamicScale
from .parallelism_config import ParallelismConfig
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, DistributedType, GradientState
from .utils.dataclasses import (
    DataLoaderConfiguration,
    DistributedDataParallelKwargs,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    KwargsHandler,
    MixedPrecisionPolicy,
    ProjectConfiguration,
    ReplicationConfig,
    TrainingHealthConfig,
)
from .utils.fault import TrainingHealthError

logger = get_logger(__name__)

__all__ = ["Accelerator"]


def check_wide_pp_limit(mesh_size: int, pp_size: int) -> None:
    """Refuse pipeline meshes whose non-pp subgroup exceeds 4 devices.

    XLA's SPMD partitioner CHECK-crashes (spmd_partitioner_util partition-
    group arithmetic) partitioning the pipeline shard_map (manual over pp,
    auto over the rest) whenever the auto subgroup exceeds 4 devices —
    reproduced under pp=2 for dp8, ddp2×fsdp4, and dp4×tp2 (every schedule:
    GPipe, 1F1B, interleaved; fused and eager), while pp4×dp4 and every
    auto<=4 composition partitions fine. The crashing CHECK lives in the
    platform-independent partitioner (spmd_partitioner_util.cc — unlike the
    CPU-only AllReducePromotion/rendezvous classes), but it has only ever
    been REPRODUCED on the CPU backend: hard-error there, warn on real TPU
    where the compiler stack differs and no evidence exists either way.
    ACCELERATE_FORCE_WIDE_PP=1 silences both once upstream is fixed."""
    from .utils.environment import parse_flag_from_env

    auto_size = mesh_size // max(pp_size, 1)
    if auto_size > 4 and not parse_flag_from_env("ACCELERATE_FORCE_WIDE_PP"):
        import jax

        msg = (
            f"pipeline parallelism with a {auto_size}-device non-pp "
            "subgroup hits an XLA SPMD-partitioner crash (partition-group "
            "CHECK) on current XLA:CPU. Keep dp*tp*cp*sp*ep <= 4 per "
            "pipeline (e.g. raise pp_size), or set "
            "ACCELERATE_FORCE_WIDE_PP=1 to try anyway."
        )
        if jax.default_backend() == "cpu":
            raise ValueError(msg)
        logger.warning(
            "%s (continuing: the crash is unreproduced on the %s backend)",
            msg, jax.default_backend(),
        )


# What the plan of a train step leaves free beyond the bytes it counts, as a
# share of the device's limit. It covers what the compiled step's table leaves
# out (on a TPU v5e the program's own code, 24-31 MB, and the batches in
# flight) and what steps dispatched ahead have been seen to add (1.33e9 B,
# 7.9% of the limit, at PR 35's step), and it keeps the plan under the fullest
# the chip is known to run a step at (90.7%): PERF.md section 6, PR 36.
_PLAN_MARGIN = 1 / 8


def _device_memory():
    """``(bytes_limit, bytes_in_use)`` of this process's first device, or
    ``None`` where the backend keeps no such count (the CPU). The one place a
    train step's plan reads the device, and what a test replaces."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))


def _plan_remat(make_jit, args, memory):
    """Walk ``REMAT_LADDER`` from its fastest rung down and keep the first
    whose compiled step fits the device: ``(jitted, facts)``.

    ``make_jit(rung)`` is the step traced with ``"auto"`` meaning that rung,
    lowered and compiled here for ``args``; the call that follows finds that
    executable and compiles nothing. It fits if its temporaries, beside its arguments or
    beside everything the process holds now (the arguments are among it),
    whichever is more, leave ``_PLAN_MARGIN`` of the limit free. A compile that
    the device's compiler refuses for memory does not fit. The last rung is
    kept whether it fits or not, as it was before there was a plan."""
    from .analysis.lowering import memory_table
    from .models.llama import REMAT_LADDER

    limit, in_use = memory
    for tried, rung in enumerate(REMAT_LADDER, start=1):
        jitted = make_jit(rung)
        try:
            table = memory_table(jitted.lower(*args).compile())
        except jax.errors.JaxRuntimeError as err:
            if rung == REMAT_LADDER[-1] or "RESOURCE_EXHAUSTED" not in str(err):
                raise
            continue
        needed = table["temp_size_in_bytes"] + max(table["argument_size_in_bytes"], in_use)
        if needed <= limit * (1 - _PLAN_MARGIN) or rung == REMAT_LADDER[-1]:
            return jitted, {
                "remat": rung, "rungs_tried": tried, "hbm_live": table["hbm_live"],
                "bytes_limit": limit, "bytes_in_use": in_use,
            }


def _is_optax_tx(obj) -> bool:
    return (
        hasattr(obj, "init")
        and hasattr(obj, "update")
        and not isinstance(obj, (Model, dict))
        and not hasattr(obj, "apply_fn")
    )


def _is_model_like(obj) -> bool:
    if isinstance(obj, Model):
        return True
    if _is_optax_tx(obj):  # optax txs are (init, update) namedtuples
        return False
    if isinstance(obj, tuple) and len(obj) == 2 and callable(obj[0]) and not callable(obj[1]):
        return True
    return False


def _is_loader_like(obj) -> bool:
    if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
        return True
    try:
        import torch.utils.data as tud

        if isinstance(obj, tud.DataLoader):
            return True
    except ImportError:
        pass
    return False


class Accelerator:
    """Single entry object for distributed TPU training
    (reference accelerator.py:184)."""

    def __init__(
        self,
        *,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        fsdp_plugin=None,
        parallelism_config: Optional[ParallelismConfig] = None,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        log_with: Optional[Union[str, list]] = None,
        rng_types: Optional[Sequence[str]] = None,
        cpu: bool = False,
        device_placement: bool = True,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[Sequence[KwargsHandler]] = None,
        health_config: Optional[TrainingHealthConfig] = None,
        replication_config: Optional[ReplicationConfig] = None,
        async_logging: bool = False,
    ):
        if project_config is not None:
            self.project_configuration = project_config
        else:
            self.project_configuration = ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # kwargs handlers (reference accelerator.py:415-452)
        self.scaler_kwargs = None
        self.mp_policy_override = None
        self.ddp_handler = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_kwargs = handler
            elif isinstance(handler, MixedPrecisionPolicy):
                self.mp_policy_override = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler
            elif isinstance(handler, DataLoaderConfiguration) and dataloader_config is None:
                dataloader_config = handler
            elif isinstance(handler, GradientAccumulationPlugin) and gradient_accumulation_plugin is None:
                gradient_accumulation_plugin = handler
            elif isinstance(handler, TrainingHealthConfig) and health_config is None:
                health_config = handler
            elif isinstance(handler, ReplicationConfig) and replication_config is None:
                replication_config = handler

        self.dataloader_config = dataloader_config or DataLoaderConfiguration()
        if fsdp_plugin is None and os.environ.get("ACCELERATE_USE_FSDP", "") == "true":
            from .utils.dataclasses import FSDPPlugin

            fsdp_plugin = FSDPPlugin()
        self.fsdp_plugin = fsdp_plugin
        self.state = AcceleratorState(
            mixed_precision=mixed_precision, cpu=cpu, parallelism_config=parallelism_config
        )
        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer

        if gradient_accumulation_plugin is None:
            steps = int(
                os.environ.get(
                    "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps
                )
            )
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=steps)
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.policy = self.mp_policy_override or MixedPrecisionPolicy.from_mixed_precision(
            self.state.mixed_precision
        )
        self.scaler: Optional[DynamicScale] = None
        if self.state.mixed_precision == "fp16":
            kw = self.scaler_kwargs.to_dict() if self.scaler_kwargs else {}
            kw.pop("enabled", None)
            self.scaler = DynamicScale(**kw)

        self.rng_types = rng_types
        self.log_with = (
            [log_with] if isinstance(log_with, str) else list(log_with or [])
        )
        self.trackers: list = []
        self.step = 0
        self.flag_tensor = None

        self._models: list[Model] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list = []
        self._custom_objects: list = []
        self._grad_fns: dict = {}
        self._fused_steps: dict = {}
        self._save_state_pre_hooks: list = []
        self._load_state_pre_hooks: list = []
        self._forced_sync = False
        self._in_accumulate = False

        # training health watchdog + non-blocking telemetry
        # (docs/fault_tolerance.md): the health ring and tracker flusher
        # are created lazily; all readbacks funnel through telemetry._fetch
        self.health_config = health_config or TrainingHealthConfig()
        self._bad_step_count = 0
        self._last_committed_checkpoint: Optional[str] = None
        self._health_ring = None
        self._health_seq = 0
        # perf observatory window mark: the interval between consecutive
        # materialized health verdicts IS the fused-step throughput, read
        # at a point that already synchronizes the host (no new readback)
        self._pw_mark = None
        self.last_health = None
        from .utils.environment import parse_flag_from_env as _flag

        self.async_logging = async_logging or _flag("ACCELERATE_ASYNC_LOGGING")
        self._tracker_flusher = None

        # checkpoint replication (docs/fault_tolerance.md "Replication &
        # elastic resume"): every committed checkpoint is mirrored to
        # durable storage by a bounded background replicator; the env path
        # lets `accelerate-tpu launch` arm it fleet-wide without code edits
        if replication_config is None:
            _target = os.environ.get("ACCELERATE_REPLICATION_TARGET")
            if _target:
                replication_config = ReplicationConfig(
                    target=_target,
                    copies=int(os.environ.get("ACCELERATE_REPLICATION_COPIES", "1")),
                    async_replicate=not _flag("ACCELERATE_REPLICATION_SYNC"),
                )
        self.replication_config = replication_config
        self._replicator = None

        self.mesh = self.state.get_device_mesh()

        # Preemption-aware saves: under `accelerate launch --handle_preemption`
        # the supervisor sets this flag so every worker checkpoints on
        # SIGTERM/SIGINT and exits cleanly (utils/fault.py).
        from .utils.environment import parse_flag_from_env

        if parse_flag_from_env("ACCELERATE_HANDLE_PREEMPTION"):
            self.install_preemption_handler()

    # ------------------------------------------------------------- properties
    @property
    def parallelism_config(self) -> ParallelismConfig:
        return self.state.parallelism_config

    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    # ------------------------------------------------- reference passthroughs
    # (reference accelerator.py properties — same observable values; the
    # engine-specific ones are documented exemptions in tests/test_api_parity)
    @property
    def multi_device(self) -> bool:
        import jax

        return len(jax.devices()) > 1

    @property
    def split_batches(self) -> bool:
        return self.dataloader_config.split_batches

    @property
    def dispatch_batches(self):
        return self.dataloader_config.dispatch_batches

    @property
    def even_batches(self) -> bool:
        return self.dataloader_config.even_batches

    @property
    def use_seedable_sampler(self) -> bool:
        return self.dataloader_config.use_seedable_sampler

    @property
    def non_blocking(self) -> bool:
        return self.dataloader_config.non_blocking

    @property
    def use_stateful_dataloader(self) -> bool:
        return self.dataloader_config.use_stateful_dataloader

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self) -> int:
        return self.project_configuration.iteration

    @property
    def is_fsdp2(self) -> bool:
        """Reference: fsdp_version == 2. Here parameter sharding IS the
        fsdp2-style per-tensor sharding whenever dp_shard is active (one
        definition — state.AcceleratorState.is_fsdp2)."""
        return self.state.is_fsdp2

    @property
    def is_composable_parallelism_enabled(self) -> bool:
        """Every strategy composes on the one mesh — True whenever a mesh
        exists (reference: fsdp2-only)."""
        return self.mesh is not None

    @property
    def should_save_model(self) -> bool:
        """Reference gates on engines that own saving (Megatron). Sharded
        saves here involve every process, so always True."""
        return True

    @property
    def optimizer_step_was_skipped(self) -> bool:
        """Whether the last optimizer step was skipped (fp16 overflow /
        accumulation gating) — reference accelerator.py property."""
        return any(opt.step_was_skipped for opt in self._optimizers)

    @property
    def fp8_backend(self):
        """"NATIVE" when fp8 is active (ops/fp8.py) — the reference reports
        which of its three engine adapters is in use."""
        return "NATIVE" if self.state.mixed_precision == "fp8" else None

    @property
    def deepspeed_plugin(self):
        """Always None: there is no DeepSpeed engine — ZeRO semantics are
        mesh shardings (docs/usage_guides/zero_on_tpu.md). Kept so
        reference-shaped `if accelerator.deepspeed_plugin:` guards run."""
        return None

    def _mesh_axis_rank(self, *axis_names: str) -> int:
        """This process's coordinate along a mesh axis (the reference's
        per-rank accessors; under SPMD, the position of this process's
        first addressable device)."""
        if self.mesh is None:
            return 0
        import jax
        import numpy as np

        axes = [a for a in axis_names if a in self.mesh.axis_names]
        if not axes or all(self.mesh.shape[a] == 1 for a in axes):
            return 0
        first = jax.local_devices()[0]
        coords = np.argwhere(self.mesh.devices == first)
        if coords.size == 0:  # device not in mesh (cpu fallback)
            return 0
        coord = dict(zip(self.mesh.axis_names, coords[0]))
        rank = 0
        for a in axes:
            rank = rank * self.mesh.shape[a] + int(coord[a])
        return rank

    @property
    def tensor_parallel_rank(self) -> int:
        return self._mesh_axis_rank("tp")

    @property
    def pipeline_parallel_rank(self) -> int:
        return self._mesh_axis_rank("pp")

    @property
    def context_parallel_rank(self) -> int:
        return self._mesh_axis_rank("cp")

    @property
    def data_parallel_rank(self) -> int:
        return self._mesh_axis_rank("dp_replicate", "dp_shard")

    @property
    def data_parallel_shard_rank(self) -> int:
        return self._mesh_axis_rank("dp_shard")

    def on_local_process(self, function=None, local_process_index: int = 0):
        """Run only on the given local process (reference decorator)."""
        return self.state._partial.on_local_process(
            function, local_process_index=local_process_index
        )

    def trigger_sync_in_backward(self, model=None) -> None:
        """Force gradient sync for the in-flight backward even
        mid-accumulation (reference accelerator.py trigger_sync_in_backward)
        WITHOUT changing the accumulation cadence. Inside ``accumulate()``
        the immediate flag covers the current microbatch; outside, the
        forced flag survives the next ``accumulate()`` entry's cadence
        recomputation so exactly one upcoming microbatch syncs."""
        self.gradient_state._set_sync_gradients(True)
        if not self._in_accumulate:
            self._forced_sync = True

    def save(self, obj, f, safe_serialization: bool = False):
        """Save honoring ProjectConfiguration.save_on_each_node (reference
        accelerator.py:save → utils save, which gates on main process /
        main-local-process itself)."""
        from .utils.other import save as _save

        _save(
            obj, f,
            save_on_each_node=getattr(
                self.project_configuration, "save_on_each_node", False
            ),
            safe_serialization=safe_serialization,
        )

    def verify_device_map(self, model) -> bool:
        """Reference: detect big-model device_maps that break DDP wrapping.
        No hook-based device maps exist here — always False."""
        return False

    @property
    def device(self):
        return self.state.device

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.num_steps = value

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    # ---------------------------------------------------------------- prepare
    def prepare(self, *args, device_placement=None):
        """Shard/wrap each object (reference accelerator.py:1414-1578).

        Accepts any mix of: :class:`Model` (or ``(apply_fn, params)`` tuples),
        ``optax`` transformations / :class:`AcceleratedOptimizer`, dataloaders
        (torch or native datasets are prepared via
        :meth:`prepare_data_loader` separately), schedule fns /
        :class:`AcceleratedScheduler`. Returns them in the same order.
        """
        result = []
        # first pass: models (optimizers need sharded params)
        prepared_models = {}
        for i, obj in enumerate(args):
            if _is_model_like(obj):
                prepared_models[i] = self.prepare_model(obj)
        for i, obj in enumerate(args):
            if i in prepared_models:
                result.append(prepared_models[i])
            elif isinstance(obj, AcceleratedOptimizer) or _is_optax_tx(obj):
                result.append(self.prepare_optimizer(obj))
            elif _is_loader_like(obj):
                result.append(self.prepare_data_loader(obj))
            elif isinstance(obj, AcceleratedScheduler) or callable(obj):
                result.append(self.prepare_scheduler(obj))
            else:
                result.append(obj)
        return result[0] if len(result) == 1 else tuple(result)

    def prepare_model(self, model: Union[Model, tuple], evaluation_mode: bool = False) -> Model:
        """Compute + apply param shardings (the GSPMD "wrap" —
        vs reference prepare_model's DDP/FSDP wrapping, accelerator.py:1769-2068)."""
        if isinstance(model, tuple):
            model = Model(model[0], model[1])
        if model.policy is None and self.state.mixed_precision != "no":
            model.policy = self.policy
        if self.state.mixed_precision == "fp8":
            if hasattr(getattr(model, "config", None), "use_fp8"):
                # fp8 projections in-model (ops/fp8.py); the bf16 policy
                # still covers non-matmul math (reference picks AO→TE→MSAMP
                # here, accelerator.py:487-503 — one native path instead)
                model.config.use_fp8 = True
            else:
                # arbitrary user models: rewrite Linear-shaped dots in the
                # traced program to the fp8 path — the prepare-level
                # analogue of reference convert_model (utils/ao.py,
                # utils/transformer_engine.py), which swaps nn.Linear
                # modules for Float8Linear/te.Linear
                from .ops.fp8 import fp8_rewrite

                model.apply_fn = fp8_rewrite(model.apply_fn)

        from .parallel.sharding import infer_shardings, apply_shardings
        from .parallel.tp import tensor_parallel_rules

        pcfg = self.parallelism_config
        layer_axis = "pp" if pcfg.pp_enabled else None
        rules = []
        if pcfg.ep_enabled:
            from .parallel.ep import expert_parallel_rules

            rules += expert_parallel_rules(layer_axis=layer_axis)
        if pcfg.tp_enabled:
            rules += tensor_parallel_rules(layer_axis=layer_axis)
        if pcfg.pp_enabled:
            # catch-all for remaining stacked layer params (norms, plain MLP
            # kernels without a TP rule): shard the layer dim over pp stages
            from jax.sharding import PartitionSpec as _P

            rules.append((r"^layers/", _P("pp")))
        # user-supplied rule extensions (FSDPPlugin / TensorParallelConfig —
        # the reference's plugin knobs, utils/dataclasses.py:1586,2295)
        if pcfg.tp_config is not None and getattr(pcfg.tp_config, "sharding_rules", None):
            rules = list(pcfg.tp_config.sharding_rules) + rules
        min_weight_size = 2**10
        if self.fsdp_plugin is not None:
            min_weight_size = self.fsdp_plugin.min_weight_size
            if self.fsdp_plugin.sharding_rules:
                rules = list(self.fsdp_plugin.sharding_rules) + rules
            if (
                self.fsdp_plugin.activation_checkpointing
                and getattr(getattr(model, "config", None), "remat_policy", None)
                in ("auto", "nothing")
            ):
                model.config.remat_policy = "minimal"
        fsdp_axes = pcfg.fsdp_dim_names
        # record for use-time gather pinning (parallel/sharding.py
        # _fsdp_use_hints): model code reconstructs storage specs in-trace.
        # The per-model copy is authoritative inside this model's apply
        # (scoped by Model._mp_apply); the shared-state copy covers paths
        # that bypass apply (pipeline stage fns).
        model._fsdp_hints = (tuple(fsdp_axes), min_weight_size)
        self.state._shared_state["fsdp_axes"] = tuple(fsdp_axes)
        self.state._shared_state["fsdp_min_weight_size"] = min_weight_size
        shardings = infer_shardings(
            model.params, self.mesh, rules=rules, fsdp_axes=fsdp_axes,
            min_weight_size=min_weight_size,
        )
        model.params = apply_shardings(model.params, shardings)
        model.shardings = shardings
        model.mesh = self.mesh

        # CP/SP: inject the mesh-aware attention (the reference instead swaps
        # torch CP buffers / registers DeepSpeed Ulysses hooks —
        # accelerator.py:1658-1671, :2386-2437)
        attention_fn = self.build_attention_fn(
            model_config=getattr(model, "config", None)
        )
        if attention_fn is not None:
            if hasattr(model, "set_attention_fn"):
                model.set_attention_fn(attention_fn)
            else:
                logger.warning(
                    "cp/sp parallelism configured but the model exposes no "
                    "set_attention_fn hook; attention will not be sequence-parallel"
                )
        if pcfg.pp_enabled:
            from .parallel.pp import make_pipeline_layer_stack
            from .utils.dataclasses import PipelineParallelConfig

            check_wide_pp_limit(self.mesh.size, self.mesh.shape.get("pp", 1))
            pp_cfg = pcfg.pp_config or PipelineParallelConfig()
            stack_fn = make_pipeline_layer_stack(self.mesh, pp_cfg.num_microbatches)
            if hasattr(model, "set_layer_stack_fn"):
                model.set_layer_stack_fn(stack_fn)
            else:
                logger.warning(
                    "pp parallelism configured but the model exposes no "
                    "set_layer_stack_fn hook; layers will not be pipelined"
                )
            if pp_cfg.schedule == "1f1b":
                if hasattr(model, "pipeline_parts"):
                    # train_step swaps in the hand-scheduled 1F1B grad path;
                    # forward/eval keeps the GPipe layer stack above
                    model._pp_1f1b_cfg = pp_cfg
                else:
                    logger.warning(
                        "pp schedule '1f1b' requested but the model exposes no "
                        "pipeline_parts contract (MoE models fold aux losses "
                        "the 1F1B path does not yet carry); falling back to "
                        "the GPipe schedule"
                    )
        if model not in self._models:
            self._models.append(model)
        return model

    def build_attention_fn(self, model_config=None):
        """The attention implementation this mesh calls for: ring attention
        over cp, Ulysses over sp, or None (single-device attention).

        ``model_config``: when the model asks for the Pallas flash kernel
        (``attention_impl="flash"``), both paths honor it — Ulysses runs it
        on the LOCAL full sequence post head-scatter, and ring attention
        runs it per ring step with LSE merging across the ring
        (ops/ring_attention.py; the allgather rotation alone keeps
        blockwise partials, which need shard-offset stats).
        """
        pcfg = self.parallelism_config
        # uniform sliding windows ride the ring/Ulysses fns. Gemma-2's
        # per-layer alternation builds WINDOWLESS on purpose: the fns accept
        # a per-call static window override (.supports_window_override), and
        # each local/global layer passes its own window — two traced
        # branches against one injected fn.
        window = getattr(model_config, "sliding_window", None)
        if getattr(model_config, "alternating_sliding_window", False):
            window = None
        # Gemma-2 tanh score capping runs inside every ring step / the
        # Ulysses inner (capping precedes the softmax the LSE merge
        # describes, so the merge math is unchanged)
        softcap = getattr(model_config, "attn_logit_softcap", None)
        if pcfg.cp_enabled:
            from .ops.ring_attention import make_ring_attention
            from .utils.dataclasses import ContextParallelConfig

            cp_cfg = pcfg.cp_config or ContextParallelConfig()
            return make_ring_attention(
                self.mesh, rotate_method=cp_cfg.rotate_method,
                kv_block=cp_cfg.kv_block,
                attention_impl=getattr(model_config, "attention_impl", "blockwise")
                or "blockwise",
                block_q=getattr(model_config, "attention_block_q", 2048),
                window=window,
                softcap=softcap,
            )
        if pcfg.sp_enabled:
            from .ops.ulysses import make_ulysses_attention

            inner = None
            if getattr(model_config, "attention_impl", None) is not None:
                from .ops.attention import dispatch_attention

                # route the local attention through the shared dispatcher so
                # the model's configured impl (flash/blockwise/xla) and its
                # guards (non-causal fallback etc.) apply post head-scatter
                inner = functools.partial(
                    dispatch_attention,
                    model_config.attention_impl,
                    kv_block=getattr(model_config, "attention_kv_block", 512),
                    block_q=getattr(model_config, "attention_block_q", 2048),
                )

            return make_ulysses_attention(
                self.mesh, inner=inner, window=window, softcap=softcap
            )
        return None

    def prepare_optimizer(self, optimizer, device_placement=None) -> AcceleratedOptimizer:
        if not isinstance(optimizer, AcceleratedOptimizer):
            optimizer = AcceleratedOptimizer(optimizer, scaler=self.scaler)
        if optimizer.opt_state is None:
            if not self._models:
                raise ValueError(
                    "prepare(optimizer) requires the model to be prepared first "
                    "(pass both to one prepare() call, model before/with optimizer)."
                )
            optimizer.init(self._models[-1])
        self._optimizers.append(optimizer)
        return optimizer

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        if not isinstance(scheduler, AcceleratedScheduler):
            scheduler = AcceleratedScheduler(
                scheduler,
                optimizer=self._optimizers[-1] if self._optimizers else None,
                step_with_optimizer=self.step_scheduler_with_optimizer,
                split_batches=self.dataloader_config.split_batches,
            )
        self._schedulers.append(scheduler)
        return scheduler

    def prepare_data_loader(self, dataloader, device_placement=None, **kwargs) -> Any:
        if isinstance(dataloader, (DataLoaderShard, DataLoaderDispatcher)):
            return dataloader
        cfg = self.dataloader_config
        kwargs.setdefault("split_batches", cfg.split_batches)
        kwargs.setdefault("even_batches", cfg.even_batches)
        kwargs.setdefault("dispatch_batches", cfg.dispatch_batches)
        kwargs.setdefault("seq_axes", self.parallelism_config.seq_dim_names)
        if cfg.data_seed is not None:
            kwargs.setdefault("seed", cfg.data_seed)
        prepared = prepare_data_loader(
            dataloader,
            mesh=self.mesh,
            rng_types=self.rng_types,
            put_on_device=self.device_placement if device_placement is None else device_placement,
            **kwargs,
        )
        self._dataloaders.append(prepared)
        return prepared

    # ------------------------------------------------------- training: eager
    def _grad_fn_for(self, loss_fn: Callable, model: Model, num_steps: int):
        key = (id(loss_fn), id(model), num_steps)
        fn = self._grad_fns.get(key)
        if fn is None:

            grad_dtype = self.ddp_handler.gradient_dtype if self.ddp_handler else None

            def wrapped(params, scale, *args, **kwargs):
                out = loss_fn(model.bind(params), *args, **kwargs)
                loss, aux = out if isinstance(out, tuple) else (out, None)
                return loss * scale / num_steps, (loss, aux)

            raw = jax.value_and_grad(wrapped, has_aux=True)
            if grad_dtype is not None:
                # gradient-compression comm hook analogue: reduce/accumulate
                # gradients in the compressed dtype
                def raw_compressed(*a, **k):
                    val, grads = raw(*a, **k)
                    return val, jax.tree_util.tree_map(
                        lambda g: g.astype(grad_dtype), grads
                    )

                fn = jax.jit(raw_compressed)
            else:
                fn = jax.jit(raw)
            self._grad_fns[key] = fn
        return fn

    def backward(self, loss_fn: Callable, *args, model: Optional[Model] = None, **kwargs):
        """Compute grads of ``loss_fn(model, *args, **kwargs)`` w.r.t. the
        model's params and accumulate them (reference accelerator.py:2818).

        The reference signature is ``backward(loss)`` on an autograd tape; JAX
        has no tape, so backward takes the loss *function* — defined ONCE
        outside the loop (its identity keys the compilation cache) — plus the
        batch. Returns the (unscaled) loss value; a ``(loss, aux)`` return
        propagates aux.
        """
        if model is None:
            if not self._models:
                raise ValueError("No prepared model; call prepare() first")
            model = self._models[-1]
        optimizer = self._optimizers[-1] if self._optimizers else None
        grad_fn = self._grad_fn_for(loss_fn, model, self.gradient_state.num_steps)
        scale = self.scaler.state["scale"] if self.scaler is not None else jnp.float32(1.0)
        (_, (loss, aux)), grads = grad_fn(model.params, scale, *args, **kwargs)
        if optimizer is None:
            raise RuntimeError(
                "backward() needs a prepared optimizer to accumulate gradients "
                "into — pass the optimizer to prepare(), or use "
                "accelerator.train_step for a self-contained compiled step."
            )
        optimizer.accumulate_grads(grads)
        self._touch_heartbeat()
        return loss if aux is None else (loss, aux)

    def _touch_heartbeat(self) -> None:
        """Liveness signal for the launch supervisor's hang watchdog: touch
        ``ACCELERATE_HEARTBEAT_FILE`` (exported by ``accelerate-tpu launch
        --watchdog_timeout``) once per training step. No-op otherwise."""
        hb = os.environ.get("ACCELERATE_HEARTBEAT_FILE")
        if hb:
            try:
                os.utime(hb, None)
            except OSError:
                pass

    def resume_from_latest(
        self, input_dir: Optional[str] = None, elastic: Optional[bool] = None
    ) -> bool:
        """Auto-resume glue for the fault-tolerant launcher: load the latest
        checkpoint under ``project_dir`` (or ``input_dir``) if one exists.
        Returns True when state was restored, False when there is nothing to
        resume from — so a script can call it unconditionally and get
        identical behavior on first launch and on a supervisor restart
        (``ACCELERATE_RESTART_COUNT`` > 0). PREPARED dataloaders resume their
        exact mid-epoch position automatically (their state rides
        ``save_state``); ``skip_first_batches`` is only for loaders the
        Accelerator does not manage — do not apply it on top of a restored
        prepared loader, that would skip twice.

        Elastic recovery (docs/fault_tolerance.md "Replication & elastic
        resume"): multi-process resumes go through **cluster consensus** —
        every host all-gathers its newest committed (index, manifest digest)
        and the gang loads the highest index committed on all hosts
        (:class:`~accelerate_tpu.utils.fault.CheckpointDivergedError` on
        content disagreement). A host missing the consensus checkpoint
        fetches it from the configured replica target. ``elastic=True``
        (default from ``ACCELERATE_ELASTIC``, exported by ``accelerate-tpu
        launch --elastic``) additionally permits resuming a checkpoint saved
        on a DIFFERENT world size, resharding onto the live mesh."""
        if elastic is None:
            from .utils.environment import parse_flag_from_env

            elastic = parse_flag_from_env("ACCELERATE_ELASTIC")
        load_kwargs = {"elastic": True} if elastic else {}
        pc = self.project_configuration
        try:
            if input_dir is None and self.num_processes > 1 and pc.project_dir:
                from . import elastic as _elastic

                base = os.path.join(pc.project_dir, "checkpoints")
                consensus = _elastic.resolve_consensus_checkpoint(base)
                if consensus is None:
                    # no host has anything locally: first launch, unless a
                    # replica set exists (every local disk was lost)
                    if self.replication_config is None:
                        return False
                    path = _elastic.ensure_local_checkpoint(
                        self.replication_config, base
                    )
                elif consensus.missing_ranks:
                    # SOME host lacks the consensus checkpoint. The fetch
                    # path is collective (ensure_local_checkpoint gathers
                    # internally), and missing_ranks is derived from the
                    # gathered views — identical on every rank — so the
                    # WHOLE gang enters it together, hosts that already
                    # hold the tree included (they no-op inside), or the
                    # whole gang raises together. Per-host branching on
                    # local_path alone would let the holders skip the
                    # fetch's collectives and wedge the job.
                    if self.replication_config is None:
                        from .utils.fault import ReplicaUnavailableError

                        raise ReplicaUnavailableError(
                            f"host(s) {sorted(consensus.missing_ranks)} do "
                            f"not hold the consensus "
                            f"checkpoint_{consensus.index} and no "
                            "ReplicationConfig is active to fetch it"
                        )
                    path = _elastic.ensure_local_checkpoint(
                        self.replication_config,
                        base,
                        name=f"checkpoint_{consensus.index}",
                        expected_digest=consensus.digest,
                    )
                else:
                    path = consensus.local_path
                self.load_state(path, **load_kwargs)
            else:
                self.load_state(input_dir, **load_kwargs)
        except FileNotFoundError:
            return False
        pc = self.project_configuration
        if input_dir is None and pc.automatic_checkpoint_naming and pc.project_dir:
            # a fresh process restarts iteration at 0 — fast-forward past the
            # checkpoints already on disk so the next save doesn't overwrite.
            # checkpoint_index-based listing skips `.tmp` staging leftovers
            # from an interrupted save (a bare int() over listdir would crash
            # on "checkpoint_2.tmp").
            from .checkpointing import checkpoint_index, list_checkpoints

            base = os.path.join(pc.project_dir, "checkpoints")
            indices = [
                checkpoint_index(os.path.basename(p))
                for p in list_checkpoints(base)
            ]
            if indices:
                pc.iteration = max(indices) + 1
        return True

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: float = 2.0):
        """Clip accumulated grads by global norm (reference accelerator.py:
        2946-3007; the XLA pre-all-reduce there is unnecessary under GSPMD —
        gradients are already global values)."""
        if not self.gradient_state.sync_gradients:
            return jnp.float32(0.0)
        if not self._optimizers:
            return jnp.float32(0.0)
        return self._optimizers[-1].clip_grad_norm_(max_norm)

    def unscale_gradients(self, optimizer=None):
        """Divide accumulated grads by the loss scale before manual gradient
        ops (reference accelerator.py unscale_gradients)."""
        if self.scaler is None:
            return
        opts = [optimizer] if optimizer is not None else self._optimizers
        for opt in opts:
            if opt._accum_grads is not None and not getattr(opt, "_unscaled", False):
                opt._accum_grads = self.scaler.unscale(opt._accum_grads)
                opt._unscaled = True

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        if not self.gradient_state.sync_gradients:
            return
        if self._optimizers:
            self._optimizers[-1].clip_grad_value_(clip_value)

    def _do_sync(self) -> None:
        """Set sync_gradients for this step (reference accelerator.py:1229)."""
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self._forced_sync = False
            self.gradient_state._set_sync_gradients(True)
        else:
            # A pending trigger_sync_in_backward forces THIS microbatch to
            # sync but leaves the step counter alone — the accumulation
            # cadence is unchanged, matching the reference's semantics of
            # syncing only the flagged backward.
            self.step += 1
            forced, self._forced_sync = self._forced_sync, False
            self.gradient_state._set_sync_gradients(
                forced or (self.step % self.gradient_state.num_steps) == 0
            )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Per-microbatch context toggling grad sync
        (reference accelerator.py:1255-1299)."""
        self._do_sync()
        self._in_accumulate = True
        try:
            yield
        finally:
            self._in_accumulate = False

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Force-disable gradient sync inside the context
        (reference accelerator.py:1132-1180). Under GSPMD this only gates the
        optimizer step — there is no per-backward all-reduce to skip; the
        compiler already defers communication to the update."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Parity shim for reference accelerator.py:1300-1413: with fixed-shape
        SPMD + even_batches padding, uneven tails cannot deadlock collectives,
        so this only optionally overrides even_batches on active loaders."""
        overridden = []
        if even_batches is not None:
            for dl in self._dataloaders:
                sampler = getattr(dl, "batch_sampler", None)
                if sampler is not None and hasattr(sampler, "even_batches"):
                    overridden.append((sampler, sampler.even_batches))
                    sampler.even_batches = even_batches
        try:
            yield
        finally:
            for sampler, old in overridden:
                sampler.even_batches = old

    # ------------------------------------------------------ training: fused
    def train_step(
        self,
        loss_fn: Callable,
        model: Optional[Model] = None,
        optimizer: Optional[AcceleratedOptimizer] = None,
        max_grad_norm: Optional[float] = None,
        donate: bool = True,
        multi_step: bool = False,
        flatten_params: bool = False,
    ) -> Callable:
        """Build ONE compiled step: forward+backward+accumulate+update fused
        (the high-MFU path; no reference equivalent — its engines keep these
        phases separate by construction).

        ``loss_fn(model_view, *batch) -> loss | (loss, aux)``. The returned
        callable ``step(*batch) -> loss`` manages params/opt-state/accum
        internally with donation, writes results back to the Model/optimizer
        objects, and honors gradient accumulation (update fires every
        ``gradient_accumulation_steps`` calls — inside the compiled program,
        no recompilation; reference GradientState semantics).

        The step plans its own memory. At one micro-batch an update it carries
        no accumulator (the gradient is the whole sum). Where the model's
        configuration leaves ``remat_policy`` at ``"auto"`` and the device says
        what it holds, the first call compiles the step rung by rung
        (``models/llama.py::REMAT_LADDER``, fastest first) and keeps the first
        whose bytes fit (``_plan_remat``); a policy that was set, a model
        without one and a backend without a memory limit get one compile, as
        ever. ``step.plan`` and the one ``train.plan`` span hold what was
        kept: ``remat``, ``rungs_tried``, ``hbm_live``, ``bytes_limit``,
        ``bytes_in_use``, ``accumulator_bytes``.

        ``multi_step=True``: the returned callable takes batches with an extra
        leading steps dim (N, ...) and runs all N steps in ONE program via
        ``lax.scan`` — amortizes dispatch overhead; returns the (N,) losses.

        ``flatten_params``: run the compiled step over fused flat buffers
        (one per dtype) instead of the (params, opt_state, accum) pytrees —
        see utils/flatbuf.py. Needs parameters that are not mesh-sharded and
        no pipeline schedule owning the parameter layout. Off unless asked
        for: the first packed step holds the optimizer state twice (the
        pytree and its packed copy), which a v5e refused at 698M parameters
        (8.4 GB of AdamW state and accumulator beside 2.8 GB of parameters on
        16 GB). The pytrees are rebuilt lazily the first time
        ``model.params`` / ``optimizer.opt_state`` is read (checkpointing
        etc.), not per step.
        """
        import optax

        model = model or self._models[-1]
        optimizer = optimizer or self._optimizers[-1]
        k = int(self.gradient_state.num_steps)
        tx = optimizer.tx
        use_scaler = self.scaler is not None
        grad_comm_dtype = self.ddp_handler.gradient_dtype if self.ddp_handler else None

        pp_1f1b_cfg = getattr(model, "_pp_1f1b_cfg", None)
        if pp_1f1b_cfg is not None and loss_fn is not getattr(
            model, "canonical_loss", loss_fn
        ):
            # the 1F1B schedule owns loss+backward via the model's
            # pipeline_parts; it cannot honor a custom objective
            logger.warning(
                "pp schedule '1f1b' computes the model's built-in loss; the "
                "custom loss_fn passed to train_step would be silently "
                "ignored — falling back to the GPipe schedule for this step "
                "function (set schedule='gpipe' to silence this warning)"
            )
            pp_1f1b_cfg = None
        il_converters = None
        il_spec = None
        if pp_1f1b_cfg is not None:
            if pp_1f1b_cfg.num_virtual_stages > 1:
                from .parallel.pp_interleaved import (
                    make_interleaved_1f1b_value_and_grad,
                    make_layout_converters,
                )

                # pre-permuted layout: the step state (params, grads, accum,
                # adam mu/nu) lives in device-major interleaved row order
                # across steps, removing the per-step param all-to-all each
                # way; model.params/optimizer.opt_state reads lazily convert
                # back to canonical (checkpoint/eval/HF boundaries).
                il_layers = jax.tree_util.tree_leaves(
                    model.params["layers"]
                )[0].shape[0]
                il_n = self.mesh.shape.get("pp", 1)
                il_v = pp_1f1b_cfg.num_virtual_stages
                abstract_params = any(
                    isinstance(p, jax.ShapeDtypeStruct)
                    for p in jax.tree_util.tree_leaves(model.params)
                )
                if not abstract_params:
                    il_converters = make_layout_converters(
                        il_layers, il_n, il_v
                    )
                    il_spec = ("pp_interleaved", il_n, il_v, il_layers)
                pipeline_vag = make_interleaved_1f1b_value_and_grad(
                    self.mesh,
                    pp_1f1b_cfg.num_microbatches,
                    pp_1f1b_cfg.num_virtual_stages,
                    pre_permuted=il_converters is not None,
                )
            else:
                from .parallel.pp_1f1b import make_1f1b_value_and_grad

                pipeline_vag = make_1f1b_value_and_grad(
                    self.mesh, pp_1f1b_cfg.num_microbatches
                )
            embed_fn, stage_fn, head_loss_fn, loss_denom_fn = model.pipeline_parts()

            def _pipeline_grads(params, scale, batch):
                """1F1B path: the schedule owns loss+backward (the model's
                built-in LM loss via pipeline_parts)."""
                if len(batch) != 1 or not isinstance(batch[0], dict):
                    raise ValueError(
                        "the 1f1b schedule expects a single dict batch — use "
                        "schedule='gpipe' for other batch layouts"
                    )
                if "segment_ids" in batch[0] or "position_ids" in batch[0]:
                    # the pipeline_parts stage contract carries only hidden
                    # states between stages; packed-batch metadata would be
                    # silently dropped (contaminated attention, unreset
                    # positions) — fail instead
                    raise ValueError(
                        "packed batches (segment_ids/position_ids) are not "
                        "supported by the 1f1b pipeline schedule — unpack "
                        "the batch or train packed data without pp"
                    )
                stage_params = params["layers"]
                io_params = {kk: v for kk, v in params.items() if kk != "layers"}
                loss, g_stage, g_io = pipeline_vag(
                    stage_params, io_params, batch[0],
                    embed_fn, stage_fn, head_loss_fn,
                    loss_denom=loss_denom_fn(batch[0]),
                    cotangent_scale=scale / k,
                )
                grads = dict(g_io)
                grads["layers"] = g_stage
                return loss, grads

        if not isinstance(flatten_params, bool):
            raise ValueError(
                f"flatten_params must be True or False; got {flatten_params!r}"
            )
        # packing is layout-preserving only for unpartitioned leaves: a
        # replicated (pure-DP) model packs fine, but FSDP/TP/EP per-dim
        # shardings do not survive 1-D concatenation into fused buffers
        params_unsharded = (
            self.mesh is None
            or self.mesh.size == 1
            or (
                model.shardings is not None
                and all(
                    getattr(s, "is_fully_replicated", False)
                    for s in jax.tree_util.tree_leaves(model.shardings)
                )
            )
        )
        if flatten_params and not params_unsharded:
            raise ValueError(
                "flatten_params=True requires unpartitioned parameters: "
                "per-leaf mesh shardings (FSDP/TP/EP) do not survive 1-D "
                "concatenation into fused buffers — XLA would replicate the "
                "full model onto every device."
            )
        # Abstract (shape-only) prepare: params are ShapeDtypeStructs. The
        # step cannot execute, but ``step.lower(*batch)`` AOT-lowers the real
        # fused program for compile/memory/collective analysis of configs far
        # too big to materialize on this host.
        abstract_mode = any(
            isinstance(p, jax.ShapeDtypeStruct)
            for p in jax.tree_util.tree_leaves(model.params)
        )
        use_flat = flatten_params and not abstract_mode
        # a pre_permuted interleaved vag consuming flat-unpacked CANONICAL
        # rows would silently run the wrong layers per stage. Unreachable
        # today (pp meshes are sharded, so flatten_params=True raised above)
        # — keep the invariant explicit.
        assert not (use_flat and il_converters is not None), (
            "flat-buffer packing cannot compose with pre-permuted "
            "interleaved-PP layout"
        )

        # ZeRO grad layout: pin each gradient to its parameter's sharding the
        # moment it is produced, so the partitioner reduces straight into the
        # shard (reduce-scatter) instead of all-reducing the FULL gradient
        # and slicing afterwards — 2x the ICI bytes on every step (observed
        # in the partitioned HLO, runs/hlo_report.md).
        grad_shardings = (
            model.shardings
            if (
                pp_1f1b_cfg is None
                and model.shardings is not None
                and self.mesh is not None
                and self.mesh.size > 1
            )
            else None
        )

        def _pin_grads(grads):
            if grad_shardings is None:
                return grads
            try:
                return jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, grads, grad_shardings
                )
            except Exception:
                return grads

        # PowerSGD comm hook: low-rank-compressed gradient reduction over
        # the dp_replicate (DCN) axis — reference POWER_SGD hook family
        # (utils/dataclasses.py:136-242). ops/powersgd.py holds the math.
        psgd_rank = None
        if self.ddp_handler is not None and self.ddp_handler.comm_hook == "powersgd":
            world = (self.mesh.shape.get("dp_replicate", 1)
                     if self.mesh is not None else 1)
            if world < 2:
                raise ValueError(
                    "comm_hook='powersgd' compresses the dp_replicate "
                    "gradient reduction — the mesh has no dp_replicate axis "
                    f"(size {world}); use dp_replicate_size >= 2"
                )
            if self.parallelism_config.pp_enabled:
                raise ValueError(
                    "comm_hook='powersgd' does not compose with pipeline "
                    "parallelism (the schedules own the backward); drop pp "
                    "or the hook"
                )
            psgd_rank = self.ddp_handler.powersgd_rank

        def fused(params, opt_state, accum, count, scaler_state, psgd_state, *batch):
            def wrapped(p):
                out = loss_fn(model.bind(p), *batch)
                loss, aux = out if isinstance(out, tuple) else (out, None)
                scale = scaler_state["scale"] if use_scaler else jnp.float32(1.0)
                return loss * scale / k, (loss, aux)

            # the step's phases as named scopes: each operation's op_name in
            # a profile starts with its phase (XProf groups by it)
            if pp_1f1b_cfg is not None:
                scale = scaler_state["scale"] if use_scaler else jnp.float32(1.0)
                with jax.named_scope("train.forward_backward"):
                    loss, grads = _pipeline_grads(params, scale, batch)
                _aux = None
            elif psgd_rank is not None:
                from .ops.powersgd import make_powersgd_grad_fn

                def local_grad(p, *b):
                    def wrapped_local(pl):
                        out = loss_fn(model.bind(pl), *b)
                        loss, aux = out if isinstance(out, tuple) else (out, None)
                        scale = (scaler_state["scale"] if use_scaler
                                 else jnp.float32(1.0))
                        return loss * scale / k, (loss, aux)

                    (_, (loss, aux)), grads = jax.value_and_grad(
                        wrapped_local, has_aux=True
                    )(p)
                    if use_scaler:
                        # unscale BEFORE compression: the persistent
                        # error-feedback/Q state must live in scale-free
                        # units or every scaler growth/backoff mis-weights
                        # the carried residual (the scale's underflow
                        # protection matters during the backward only)
                        inv = 1.0 / scaler_state["scale"]
                        grads = jax.tree_util.tree_map(
                            lambda g: g * inv, grads
                        )
                    return loss, aux, grads

                psgd_fn = make_powersgd_grad_fn(
                    self.mesh, local_grad, params, psgd_rank
                )
                with jax.named_scope("train.forward_backward"):
                    loss, _aux, grads, psgd_state = psgd_fn(
                        params, psgd_state, *batch
                    )
                if use_scaler:
                    # re-apply the scale so the shared accumulate/
                    # finite-check/unscale path downstream is unchanged
                    grads = jax.tree_util.tree_map(
                        lambda g: g * scaler_state["scale"], grads
                    )
            else:
                with jax.named_scope("train.forward_backward"):
                    (_, (loss, _aux)), grads = jax.value_and_grad(wrapped, has_aux=True)(params)
            if grad_comm_dtype is not None:
                # comm-hook compression: gradients reduce/accumulate in the
                # compressed dtype (same semantic as the eager path)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(grad_comm_dtype), grads
                )
            grads = _pin_grads(grads)

            def apply_update(params, opt_state, g, scaler_state):
                # ``g``: the gradients of the update, summed over its ``k``
                # micro-batches
                if grad_comm_dtype is not None:
                    g = jax.tree_util.tree_map(
                        lambda x, p: x.astype(p.dtype), g, params
                    )
                if use_scaler:
                    inv = 1.0 / scaler_state["scale"]
                    g = jax.tree_util.tree_map(lambda x: x * inv, g)
                if max_grad_norm is not None:
                    with jax.named_scope("train.clip"):
                        norm = optax.global_norm(g)
                        factor = jnp.minimum(1.0, max_grad_norm / (norm + 1e-6))
                        g = jax.tree_util.tree_map(lambda x: x * factor, g)
                if use_scaler:
                    finite = jnp.bool_(True)
                    for leaf in jax.tree_util.tree_leaves(g):
                        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
                    with jax.named_scope("train.optimizer"):
                        updates, maybe_os = tx.update(g, opt_state, params)
                        new_params = optax.apply_updates(params, updates)
                    new_params = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(finite, new, old), new_params, params
                    )
                    new_os = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(finite, new, old), maybe_os, opt_state
                    )
                    # full DynamicScale semantics (growth + backoff), matching
                    # the eager path's scaler.update()
                    scale, good = scaler_state["scale"], scaler_state["good_steps"]
                    grown = good + 1 >= self.scaler.growth_interval
                    new_scale = jnp.where(
                        finite,
                        jnp.where(grown, scale * self.scaler.growth_factor, scale),
                        scale * self.scaler.backoff_factor,
                    )
                    new_good = jnp.where(
                        finite, jnp.where(grown, 0, good + 1), 0
                    ).astype(good.dtype)
                    scaler_state = {"scale": new_scale, "good_steps": new_good}
                    params, opt_state = new_params, new_os
                else:
                    with jax.named_scope("train.optimizer"):
                        updates, opt_state = tx.update(g, opt_state, params)
                        params = optax.apply_updates(params, updates)
                return params, opt_state, scaler_state

            if k > 1:
                def update_and_zero(operand):
                    params, opt_state, accum, scaler_state = operand
                    params, opt_state, scaler_state = apply_update(*operand)
                    with jax.named_scope("train.accumulate"):
                        accum = jax.tree_util.tree_map(jnp.zeros_like, accum)
                    return params, opt_state, accum, scaler_state

                with jax.named_scope("train.accumulate"):
                    accum = jax.tree_util.tree_map(jnp.add, accum, grads)
                count = (count + 1) % k
                params, opt_state, accum, scaler_state = jax.lax.cond(
                    count == 0, update_and_zero, lambda op: op,
                    (params, opt_state, accum, scaler_state),
                )
                # pin the accum OUTPUT to the grad shardings: the zeroed accum
                # is a fresh broadcast whose sharding the partitioner picks
                # freely; left unpinned it can come back replicated, so call
                # N+1's input sharding differs from call N's and the whole
                # fused program compiles a second signature
                # (test_train_step_compiles_once_sharded)
                accum = _pin_grads(accum)
            else:
                # one micro-batch an update: its gradient is the whole sum;
                # ``accum`` stays the empty tree it came in as and ``count`` 0
                params, opt_state, scaler_state = apply_update(
                    params, opt_state, grads, scaler_state
                )
            return params, opt_state, accum, count, scaler_state, psgd_state, loss

        if use_flat:
            from .utils.flatbuf import build_pack_spec, pack_tree, unpack_tree

            param_spec = build_pack_spec(model.params)
            opt_spec = build_pack_spec(optimizer.opt_state)
            # no accumulator at one micro-batch an update: ``pa`` is the
            # empty tree and passes through as it is
            accum_spec = build_pack_spec(
                model.params,
                dtype_of=(lambda p: grad_comm_dtype) if grad_comm_dtype is not None else None,
            ) if k > 1 else None

            def core(pp, po, pa, count, scaler_state, psgd_state, *batch):
                params = unpack_tree(param_spec, pp)
                opt_state = unpack_tree(opt_spec, po)
                accum = unpack_tree(accum_spec, pa) if k > 1 else pa
                params, opt_state, accum, count, scaler_state, psgd_state, loss = fused(
                    params, opt_state, accum, count, scaler_state, psgd_state, *batch
                )
                return (
                    pack_tree(param_spec, params),
                    pack_tree(opt_spec, opt_state),
                    pack_tree(accum_spec, accum) if k > 1 else accum,
                    count,
                    scaler_state,
                    psgd_state,
                    loss,
                )

            _pack_params = jax.jit(functools.partial(pack_tree, param_spec))
            _pack_opt = jax.jit(functools.partial(pack_tree, opt_spec))
            _unpack_params = jax.jit(functools.partial(unpack_tree, param_spec))
            _unpack_opt = jax.jit(functools.partial(unpack_tree, opt_spec))
        else:
            core = fused

        if multi_step:

            def multi(params, opt_state, accum, count, scaler_state, psgd_state, *batches):
                def body(carry, batch):
                    params, opt_state, accum, count, scaler_state, psgd_state = carry
                    params, opt_state, accum, count, scaler_state, psgd_state, loss = core(
                        params, opt_state, accum, count, scaler_state, psgd_state, *batch
                    )
                    return (params, opt_state, accum, count, scaler_state, psgd_state), loss

                (params, opt_state, accum, count, scaler_state, psgd_state), losses = jax.lax.scan(
                    body, (params, opt_state, accum, count, scaler_state, psgd_state), batches
                )
                return params, opt_state, accum, count, scaler_state, psgd_state, losses

            target = multi
        else:
            target = core
        # arg 5 is the powersgd state (error feedback is param-sized); an
        # empty dict when the hook is off, so donating it is always safe
        donate_args = (0, 1, 2, 5) if donate else ()

        from .models.llama import REMAT_LADDER, auto_remat

        def make_jit(rung):
            """The step's program with ``remat_policy="auto"`` meaning
            ``rung``. The rung is set inside the traced function, a new one a
            rung: jit caches a trace by its function and the arguments' types,
            and sees no configuration that the function reads."""
            @functools.wraps(target)  # a profile names the program by it
            def program(*args):
                with auto_remat(rung):
                    return target(*args)

            return jax.jit(program, donate_argnums=donate_args)

        accum_dtype_of = (
            (lambda p: grad_comm_dtype) if grad_comm_dtype is not None else (lambda p: p.dtype)
        )
        if k == 1:
            # one micro-batch an update: the step carries no accumulator
            accum_init = {}
        elif use_flat:
            accum_init = tuple(
                jnp.zeros((size,), dtype=dt)
                for size, dt in zip(accum_spec.buffer_sizes, accum_spec.buffer_dtypes)
            )
        elif abstract_mode:
            # shape-only accum, sharded like the params (its steady state)
            accum_init = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(
                    p.shape, accum_dtype_of(p), sharding=getattr(p, "sharding", None)
                ),
                model.params,
            )
        else:
            # born with the parameter's own sharding: zeros made on the
            # default device and resharded afterwards put the whole
            # accumulator on the first chip, which a v5e refused for 2.0B
            # parameters over four chips (8 GB beside its 6 GB of shards)
            accum_init = jax.tree_util.tree_map(
                lambda p: jnp.zeros(
                    p.shape, dtype=accum_dtype_of(p),
                    device=getattr(p, "sharding", None),
                ),
                model.params,
            )
        if psgd_rank is not None:
            from .ops.powersgd import init_powersgd_state

            world = self.mesh.shape["dp_replicate"]
            # handles abstract (ShapeDtypeStruct) params too, attaching the
            # err shardings so step.lower/memory_analysis see the real layout
            psgd_init = init_powersgd_state(
                model.params, psgd_rank, world, mesh=self.mesh
            )
        else:
            psgd_init = {}
        state = {
            "accum": accum_init,
            "count": jnp.int32(0),
            "scaler": self.scaler.state if use_scaler else {"scale": jnp.float32(1.0), "good_steps": jnp.int32(0)},
            "psgd": psgd_init,
        }
        if not abstract_mode:
            # Commit the initial state NOW with the shardings the compiled
            # call's outputs will carry. Freshly created arrays (jnp.zeros /
            # jnp.int32) carry SingleDeviceShardings with no mesh in their
            # aval, while every output of the compiled call is NamedSharded
            # over the prepare-time mesh — pjit keys its cache on exactly
            # that, so without this, call 0 and call 1 compile TWO copies of
            # the full fused program (a whole extra multi-second XLA compile
            # inside the first *timed* step; found via
            # benchmarks/overhead_ab.py, pinned by
            # tests/test_accelerator.py::test_train_step_compiles_once).
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                replicated = NamedSharding(self.mesh, PartitionSpec())
                state["count"] = jax.device_put(state["count"], replicated)
                state["scaler"] = jax.device_put(state["scaler"], replicated)
                # accum lives sharded like the params/grads (its steady
                # state); replicating it on a >1 mesh would both miss the
                # cache AND waste memory, so fall back to jit's own
                # placement when no param shardings exist to mirror
                accum_sh = grad_shardings if grad_shardings is not None else model.shardings
                if k == 1:
                    accum_sh = None  # the empty tree: nothing to place
                elif use_flat or self.mesh.size == 1:
                    accum_sh = replicated
                if accum_sh is not None:
                    state["accum"] = jax.device_put(state["accum"], accum_sh)
                # psgd state is committed by init_powersgd_state (mesh-aware)
            else:
                state = jax.device_put(state)

        # What the step saves for its backward pass. A policy that the model's
        # configuration states stands; ``"auto"`` is the ladder's last rung
        # until the first call has the batch to compile with and, where the
        # device says what it holds, walks the ladder (``_plan_remat``).
        policy = getattr(getattr(model, "config", None), "remat_policy", None)
        plan = {
            "remat": REMAT_LADDER[-1] if policy == "auto" else policy,
            "rungs_tried": 0, "hbm_live": None, "bytes_limit": None, "bytes_in_use": None,
            "accumulator_bytes": sum(
                leaf.size * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(accum_init)
            ),
        }

        planned = False

        def make_plan(args):
            """The first call's work: one ``train.plan`` span a step built."""
            with tracing.span("train.plan", accumulator_bytes=plan["accumulator_bytes"]) as sp:
                memory = _device_memory() if policy == "auto" else None
                if memory is not None:
                    step.jitted, facts = _plan_remat(make_jit, args, memory)
                    plan.update(facts)
                for key, value in plan.items():
                    sp.set(key, value)

        def step(*batch):
            nonlocal planned
            if use_flat:
                pp = model._packed_for(param_spec)
                if pp is None:
                    pp = _pack_params(model.params)
                    # adopt immediately: drops the pytree so params are not
                    # resident twice for the whole compiled call, and keeps
                    # the model valid if the step itself fails (OOM retry)
                    model._set_packed_params(pp, param_spec, _unpack_params)
                po = optimizer._packed_for(opt_spec)
                if po is None:
                    po = _pack_opt(optimizer.opt_state)
                    optimizer._set_packed_opt_state(po, opt_spec, _unpack_opt)
                in_params, in_opt = pp, po
            elif il_converters is not None:
                # interleaved layout adoption (same lazy contract as the
                # flat buffers: reads of model.params/optimizer.opt_state
                # convert back to canonical row order on demand)
                to_il, to_can = il_converters
                pp = model._packed_for(il_spec)
                if pp is None:
                    pp = to_il(model.params)
                    model._set_packed_params(pp, il_spec, to_can)
                po = optimizer._packed_for(il_spec)
                if po is None:
                    po = to_il(optimizer.opt_state)
                    optimizer._set_packed_opt_state(po, il_spec, to_can)
                in_params, in_opt = pp, po
            else:
                in_params, in_opt = model.params, optimizer.opt_state
            args = (in_params, in_opt, state["accum"], state["count"],
                    state["scaler"], state["psgd"], *batch)
            if not planned:
                make_plan(args)
                planned = True
            # host-side dispatch span only (the fused program runs async on
            # device), one every step: what the host spends to send a step
            with tracing.span(
                "train.step", step=optimizer._step_count, flat=use_flat,
                remat=plan["remat"],
            ):
                params, opt_state, accum, count, scaler_state, psgd_state, loss = (
                    step.jitted(*args)
                )
            if use_flat:
                model._set_packed_params(params, param_spec, _unpack_params)
                optimizer._set_packed_opt_state(opt_state, opt_spec, _unpack_opt)
            elif il_converters is not None:
                model._set_packed_params(params, il_spec, il_converters[1])
                optimizer._set_packed_opt_state(
                    opt_state, il_spec, il_converters[1]
                )
            else:
                model.params = params
                optimizer.opt_state = opt_state
            state["accum"], state["count"], state["scaler"] = accum, count, scaler_state
            state["psgd"] = psgd_state
            if use_scaler:
                self.scaler.state = scaler_state
            optimizer._step_count += 1
            self._touch_heartbeat()
            return loss

        def lower(*batch):
            """AOT-lower the fused step (``jax.jit(...).lower``) against the
            current params/opt-state avals and abstract batch leaves — the
            compile-analysis path (HLO text, memory_analysis, cost_analysis)
            that works even for shape-only prepared models. Batch leaves may
            be arrays or ShapeDtypeStructs."""
            if use_flat:
                in_params = tuple(
                    jax.ShapeDtypeStruct((size,), dt)
                    for size, dt in zip(param_spec.buffer_sizes, param_spec.buffer_dtypes)
                )
                in_opt = tuple(
                    jax.ShapeDtypeStruct((size,), dt)
                    for size, dt in zip(opt_spec.buffer_sizes, opt_spec.buffer_dtypes)
                )
            else:
                in_params, in_opt = model.params, optimizer.opt_state
            return step.jitted.lower(
                in_params, in_opt, state["accum"], state["count"],
                state["scaler"], state["psgd"], *batch,
            )

        step.jitted = make_jit(REMAT_LADDER[-1])
        step.plan = plan
        step.lower = lower
        step.abstract = abstract_mode
        return step

    def eval_step(self, eval_fn: Callable, model: Optional[Model] = None) -> Callable:
        """Compiled forward-only step: ``eval_fn(model_view, *batch)`` jitted
        over the current params (no donation — params are reused)."""
        model = model or self._models[-1]

        def fused(params, *batch):
            return eval_fn(model.bind(params), *batch)

        compiled = jax.jit(fused)

        def step(*batch):
            return compiled(model.params, *batch)

        return step

    # ------------------------------------------------------------ collectives
    def gather(self, tensor):
        from .ops.operations import gather

        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather eval outputs, dropping the duplicate samples introduced by
        batch padding on the final batch (reference accelerator.py:3068-3140)."""
        from .ops.operations import find_batch_size, gather, gather_object

        # non-tensor payloads (lists of strings, nested python objects) take
        # the object path (reference accelerator.py:3068 try/except TypeError)
        if use_gather_object or find_batch_size(input_data) is None:
            return gather_object(input_data)
        data = gather(input_data)
        gs = self.gradient_state
        if gs.end_of_dataloader and gs.remainder > 0:
            from .ops.operations import recursively_apply

            rem = gs.remainder
            data = recursively_apply(lambda t: t[:rem], data)
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        from .ops.operations import reduce

        return reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        from .ops.operations import pad_across_processes

        return pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # -------------------------------------------------------- process control
    def wait_for_everyone(self, tag: str = "accelerate_tpu.Accelerator.wait_for_everyone"):
        self.state.wait_for_everyone(tag)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index=process_index)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    # --------------------------------------------------------------- triggers
    def set_trigger(self):
        """Set a breakpoint flag observable by all processes
        (reference accelerator.py:2852-2909)."""
        self.flag_tensor = True

    def check_trigger(self) -> bool:
        from .ops.operations import gather_object

        flags = gather_object([bool(self.flag_tensor)])
        if any(flags):
            self.flag_tensor = False
            return True
        return False

    # ------------------------------------------------------------ persistence
    def register_for_checkpointing(self, *objects):
        """Track custom stateful objects for save/load_state
        (reference accelerator.py:3557-3582)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"Objects must expose state_dict/load_state_dict: {invalid}"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable) -> None:
        """hook(models, weights_placeholder, output_dir) runs before
        save_state writes (reference accelerator.py register_save_state_pre_hook)."""
        self._save_state_pre_hooks.append(hook)

    def register_load_state_pre_hook(self, hook: Callable) -> None:
        self._load_state_pre_hooks.append(hook)

    def save_state(self, output_dir: Optional[str] = None, **save_kwargs) -> str:
        from .checkpointing import _resolve_dir, save_accelerator_state

        output_dir = _resolve_dir(self, output_dir, for_save=True)
        for hook in self._save_state_pre_hooks:
            hook(self._models, None, output_dir)
        self._touch_heartbeat()  # a long orbax write is progress, not a hang
        result = save_accelerator_state(self, output_dir, **save_kwargs)
        if not save_kwargs.get("async_save"):
            self._last_committed_checkpoint = result
        self._touch_heartbeat()
        return result

    def load_state(self, input_dir: Optional[str] = None, **load_kwargs) -> None:
        from .checkpointing import _resolve_for_load, load_accelerator_state, wait_for_async_saves

        # join (and commit) any in-flight async save first, so latest-committed
        # resolution below can see it
        wait_for_async_saves()
        input_dir = _resolve_for_load(self, input_dir)
        for hook in self._load_state_pre_hooks:
            hook(self._models, input_dir)
        self._touch_heartbeat()
        load_accelerator_state(self, input_dir, **load_kwargs)
        self._touch_heartbeat()

    def wait_for_async_saves(self) -> None:
        """Join in-flight async checkpoint writes and run their deferred
        atomic commits (module-level :func:`checkpointing.wait_for_async_saves`)."""
        from .checkpointing import wait_for_async_saves

        wait_for_async_saves()

    # ------------------------------------------------------------ replication
    def _get_replicator(self):
        if self.replication_config is None:
            return None
        if self._replicator is None:
            from .elastic import CheckpointReplicator

            self._replicator = CheckpointReplicator(self.replication_config)
        return self._replicator

    def _submit_replication(self, committed_dir: str) -> None:
        """Post-commit hook (called by ``checkpointing._commit_staged`` on
        the main process): hand the durable checkpoint to the background
        replicator. With ``async_replicate=False`` the mirror runs inline
        and failures raise out of ``save_state`` — the checkpoint itself is
        already committed either way."""
        if self.replication_config is None or not self.is_main_process:
            return
        self._get_replicator().submit(committed_dir)

    def wait_for_replication(self, timeout: Optional[float] = None) -> None:
        """Drain the background checkpoint replicator: block until every
        submitted mirror finished, then surface the first deferred mirror
        error. Called by ``end_training``, the preemption handler, and
        atexit — the replica set never ends a run half-mirrored silently."""
        if self._replicator is not None:
            self._replicator.drain(timeout=timeout)

    def install_preemption_handler(self, **kwargs) -> bool:
        """Checkpoint-then-exit on SIGTERM/SIGINT (TPU preemption /
        maintenance eviction). See :func:`utils.fault.install_preemption_handler`;
        auto-enabled under ``accelerate-tpu launch --handle_preemption``."""
        from .utils.fault import install_preemption_handler

        return install_preemption_handler(self, **kwargs)

    # ------------------------------------------------------- health watchdog
    def check_step_health(self, loss=None, grads=None, grad_norm=None) -> bool:
        """Training health watchdog: validate this step's ``loss`` (and, with
        ``health_config.check_grads``, the gradient pytree) for NaN/Inf and
        apply the configured policy. Returns True when the step is healthy
        (callers should then ``optimizer.step()`` as usual) and False when
        the step must be discarded:

        * ``"raise"`` — raise :class:`TrainingHealthError`;
        * ``"skip"`` — zero the accumulated grads and continue;
        * ``"restore"`` — reload the newest committed checkpoint, then
          continue.

        ``max_bad_steps`` consecutive unhealthy steps raise regardless of
        policy. The finiteness of the loss and *all* float grad leaves is
        tree-reduced on device by one fused ``telemetry.health_summary``
        program, so the host reads back exactly ONE tiny scalar array per
        call — never one transfer per gradient leaf. ``grad_norm`` (or the
        norm the optimizer's ``clip_grad_norm_`` already computed) rides
        along in the same transfer and lands in ``self.last_health``.

        With ``health_config.sync=True`` (default) the verdict for this
        step is applied before returning — a per-call host sync point.
        With ``sync=False`` the summary is enqueued on a deferred-readback
        ring and the verdict applied (and returned) is the one from
        ``readback_depth`` steps ago, keeping the dispatch pipeline full;
        call :meth:`health_drain` (``end_training`` does) to flush the
        tail. See docs/fault_tolerance.md for the latency/exactness
        trade-off."""
        from . import telemetry

        cfg = self.health_config
        if cfg.check_grads:
            if grads is None:
                for opt in self._optimizers:
                    if opt._accum_grads is not None:
                        grads = opt._accum_grads
                        break
            if grad_norm is None:
                # reuse the clipping reduction instead of re-reducing
                for opt in self._optimizers:
                    if opt._last_grad_norm is not None:
                        grad_norm = opt._last_grad_norm
                        break
        else:
            grads = None
        summary = telemetry.health_summary(loss, grads, grad_norm)
        step = self._health_seq
        self._health_seq += 1
        if cfg.sync:
            verdict = self._apply_health_verdict(
                telemetry.read_summary(summary, step)
            )
            self._pw_note_train(1)
            return verdict
        if self._health_ring is None:
            self._health_ring = telemetry.DeferredReadbackRing(cfg.readback_depth)
        ok = True
        matured_n = 0
        for s, matured in self._health_ring.push((step, summary)):
            ok = self._apply_health_verdict(telemetry.read_summary(matured, s)) and ok
            matured_n += 1
        self._pw_note_train(matured_n)
        return ok

    def health_drain(self) -> bool:
        """Read back and apply every verdict still pending on the deferred
        ring (``health_config.sync=False``), restoring exact per-step
        semantics at a boundary — end of epoch, before a checkpoint you
        must trust, or in tests. Returns True iff every drained step was
        healthy. No-op (True) in sync mode."""
        from . import telemetry

        ok = True
        ring = self._health_ring
        if ring is None:
            return True
        with tracing.span("train.ring_drain", pending=len(ring)):
            while len(ring):
                # popleft one at a time: a restore verdict clears the ring
                # (the newer in-flight summaries predate the reload — stale)
                step, summary = ring.popleft()
                ok = self._apply_health_verdict(telemetry.read_summary(summary, step)) and ok
        return ok

    def _pw_note_train(self, verdicts: int) -> None:
        """Bill the wall time since the previous materialized health
        verdict to the fused train step (perf observatory window
        accounting, docs/observability.md). A verdict readback already
        synchronized the host, so this adds a clock read at a sync point
        and nothing else; ``verdicts == 0`` (deferred ring still
        filling) leaves the window open."""
        if verdicts <= 0:
            return
        from . import perfwatch

        now = time.monotonic()
        mark, self._pw_mark = self._pw_mark, now
        if mark is None:
            return
        perfwatch.get_watch().record(
            f"train.{self._pw_variant()}/fused_train_step",
            (now - mark) / verdicts,
            calls=verdicts,
        )

    def _pw_variant(self) -> str:
        """The baseline program variant this process's mesh matches
        (``runs/perf_baseline.json`` keys: dp8, fsdp8, tp2, hsdp2x4)."""
        pc = self.parallelism_config
        r = getattr(pc, "dp_replicate_size", 1) or 1
        s = getattr(pc, "dp_shard_size", 1) or 1
        t = getattr(pc, "tp_size", 1) or 1
        if t > 1:
            return f"tp{t}"
        if r > 1 and s > 1:
            return f"hsdp{r}x{s}"
        if s > 1:
            return f"fsdp{s}"
        return f"dp{r}"

    def _apply_health_verdict(self, health) -> bool:
        """Apply the configured nonfinite policy to one realized
        :class:`telemetry.StepHealth` verdict (PR-1 semantics, shared by
        the sync path, the ring, and :meth:`health_drain`)."""
        cfg = self.health_config
        self.last_health = health
        if health.healthy:
            self._bad_step_count = 0
            return True

        self._bad_step_count += 1
        if cfg.nonfinite_policy == "raise":
            raise TrainingHealthError(
                f"non-finite loss/gradients at health step {health.step} "
                f"(nonfinite_policy='raise')"
            )
        if self._bad_step_count >= cfg.max_bad_steps:
            raise TrainingHealthError(
                f"{self._bad_step_count} consecutive non-finite steps — "
                f"exceeded max_bad_steps={cfg.max_bad_steps} under "
                f"nonfinite_policy={cfg.nonfinite_policy!r}"
            )
        if cfg.nonfinite_policy == "skip":
            logger.warning(
                f"non-finite loss/gradients at health step {health.step}; "
                f"skipping step ({self._bad_step_count}/{cfg.max_bad_steps} "
                f"consecutive)"
            )
            for opt in self._optimizers:
                opt.zero_grad()
            return False
        # "restore"
        logger.warning(
            f"non-finite loss/gradients at health step {health.step}; restoring "
            f"last committed checkpoint ({self._bad_step_count}/"
            f"{cfg.max_bad_steps} consecutive)"
        )
        for opt in self._optimizers:
            opt.zero_grad()
        if self._health_ring is not None:
            self._health_ring.clear()
        self.load_state(self._last_committed_checkpoint)
        return False

    def save_model(self, model: Model, save_directory: str, max_shard_size: str = "10GB", safe_serialization: bool = True):
        from .checkpointing import save_model_checkpoint

        return save_model_checkpoint(model, save_directory, max_shard_size=max_shard_size)

    def get_state_dict(self, model: Model, unwrap: bool = True):
        return model.state_dict()

    def unwrap_model(self, model: Model, keep_fp32_wrapper: bool = True) -> Model:
        return model

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def free_memory(self, *objects):
        """Release prepared-object references + compiled caches
        (reference accelerator.py:3902)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._grad_fns.clear()
        self._fused_steps.clear()
        from .utils.memory import release_memory

        return release_memory(*objects)

    def clear(self, *objects):
        return self.free_memory(*objects)

    # -------------------------------------------------------------- trackers
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: Optional[dict] = None):
        from .tracking import filter_trackers

        if self._tracker_flusher is not None:
            flusher, self._tracker_flusher = self._tracker_flusher, None
            flusher.close()
        init_kwargs = init_kwargs or {}
        self.trackers = []
        for tracker_cls in filter_trackers(self.log_with, self.project_configuration.logging_dir):
            name = tracker_cls.name
            tracker = tracker_cls(
                project_name,
                logging_dir=self.project_configuration.logging_dir,
                **init_kwargs.get(name, {}),
            )
            tracker.start()
            if config is not None:
                tracker.store_init_configuration(config)
            self.trackers.append(tracker)
        if self.async_logging and self.is_main_process:
            from . import telemetry

            self._tracker_flusher = telemetry.AsyncTrackerFlusher(self.trackers)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not initialized")

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: Optional[dict] = None):
        """Log ``values`` to every initialized tracker. Values may be device
        ``jax.Array`` scalars; with ``async_logging`` they are enqueued as-is
        (no readback — the hot path never blocks) and materialized by the
        background flusher, which also batches file writes. Without async
        logging, values pass straight to each tracker synchronously."""
        if not self.is_main_process:
            return
        log_kwargs = log_kwargs or {}
        if self._tracker_flusher is not None:
            self._tracker_flusher.submit(values, step, log_kwargs)
            return
        for tracker in self.trackers:
            tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def flush_trackers(self):
        """Block until every ``log()`` call so far is durably written
        (no-op without ``async_logging``); re-raise deferred tracker errors."""
        if self._tracker_flusher is not None:
            self._tracker_flusher.flush()

    def end_training(self):
        # a checkpoint still writing on background threads must reach its
        # atomic commit before the process is allowed to wind down
        from .checkpointing import wait_for_async_saves

        wait_for_async_saves()
        try:
            # the replicator drains AFTER async saves land (their commits are
            # what feed it); deferred mirror errors surface here, not atexit
            self.wait_for_replication()
        finally:
            try:
                # pending deferred health verdicts are applied before shutdown —
                # a tail-step NaN still raises/skips/restores per policy
                self.health_drain()
            finally:
                try:
                    if self._tracker_flusher is not None:
                        flusher, self._tracker_flusher = self._tracker_flusher, None
                        flusher.close()
                finally:
                    for tracker in self.trackers:
                        tracker.finish()

    # ------------------------------------------------------------------ misc
    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Parity context (reference accelerator.py:4178): precision is a
        policy applied in the model's compiled forward, so there is nothing to
        toggle dynamically — the context exists so reference-shaped loops run
        unchanged."""
        if autocast_handler is not None:
            logger.warning(
                "accelerator.autocast(autocast_handler=...) has no dynamic "
                "effect here: precision is a MixedPrecisionPolicy compiled "
                "into the model's forward (set mixed_precision=... on the "
                "Accelerator or model.policy before prepare). The handler "
                "is ignored."
            )
        yield

    @contextlib.contextmanager
    def profile(self, profile_handler=None):
        """Capture an XLA trace viewable in TensorBoard/Perfetto
        (reference accelerator.py:4203-4260 exports Chrome traces)."""
        handler = profile_handler
        log_dir = None
        if handler is not None and getattr(handler, "output_trace_dir", None):
            log_dir = handler.output_trace_dir
        elif self.project_configuration.logging_dir:
            log_dir = os.path.join(self.project_configuration.logging_dir, "profile")
        if log_dir is None:
            yield None
            return
        os.makedirs(log_dir, exist_ok=True)
        with jax.profiler.trace(log_dir):
            yield None
        if handler is not None and handler.on_trace_ready is not None:
            handler.on_trace_ready(log_dir)

    @contextlib.contextmanager
    def maybe_context_parallel(self, buffers=None, buffer_seq_dims=None, no_restore_buffers=None):
        """Parity context (reference accelerator.py:4111-4175): CP here is a
        mesh axis + ring-attention kernel chosen at prepare time, not a
        runtime buffer rewrite, so this is informational."""
        if (
            buffers is not None or buffer_seq_dims is not None or no_restore_buffers is not None
        ) and not self.parallelism_config.cp_enabled:
            logger.warning(
                "maybe_context_parallel received buffers but context "
                "parallelism is not enabled — unlike the reference, CP here "
                "is not a runtime buffer rewrite: set ParallelismConfig("
                "cp_size=...) so prepare() installs the ring-attention path. "
                "The buffer arguments are ignored either way."
            )
        yield

    def __repr__(self):
        return (
            f"Accelerator(distributed_type={self.distributed_type.value}, "
            f"num_devices={self.state.num_devices}, mixed_precision={self.mixed_precision!r}, "
            f"parallelism={self.parallelism_config!r})"
        )
