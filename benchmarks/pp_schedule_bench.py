"""Pipeline-schedule microbenchmark: GPipe vs 1F1B.

Measures, per schedule: trace+compile wall (the GPipe loop is Python-unrolled
in the microbatch count; 1F1B is a fori_loop), steady-state step wall, and
the analytic live-activation bound (GPipe autodiff saves every microbatch's
stage inputs; 1F1B keeps a ring of n_stages+1). Run on the virtual 8-device
CPU mesh:

  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/pp_schedule_bench.py

Prints one JSON line per (schedule, num_microbatches) config.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import json
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from accelerate_tpu import Accelerator
# the SAME schedule model graftcheck Level 6 gates (G505): the bench
# reports its measured bubble against the identical helper, so the static
# budget and this benchmark cannot diverge
from accelerate_tpu.analysis.perf import bubble_fraction
from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss
from accelerate_tpu.parallelism_config import ParallelismConfig
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
from accelerate_tpu.utils.dataclasses import PipelineParallelConfig


def bench(schedule: str, num_microbatches: int, steps: int = 6, virtual: int = 1):
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    pp = 4
    config = LlamaConfig.tiny(
        num_hidden_layers=8, hidden_size=128, intermediate_size=256,
        max_position_embeddings=128, compute_dtype=jnp.float32,
    )
    accelerator = Accelerator(
        parallelism_config=ParallelismConfig(
            pp_size=pp, dp_shard_size=2,
            pp_config=PipelineParallelConfig(
                num_microbatches=num_microbatches, schedule=schedule,
                num_virtual_stages=virtual,
            ),
        )
    )
    model, optimizer = accelerator.prepare(create_llama(config, seed=0), optax.sgd(1e-2))
    step = accelerator.train_step(llama_loss, max_grad_norm=None)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(
            0, config.vocab_size, size=(num_microbatches * 2, 128)
        ).astype(np.int32)
    }
    batch = jax.device_put(batch)

    t0 = time.perf_counter()
    loss = step(batch)  # trace + compile + first run
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(batch)
    jax.block_until_ready(loss)
    step_s = (time.perf_counter() - t0) / steps

    n = pp
    m = num_microbatches
    live = (n + 1) if schedule == "1f1b" else m  # stage-input activations held
    if virtual > 1:
        from accelerate_tpu.parallel.pp_interleaved import build_interleaved_schedule

        sch = build_interleaved_schedule(n, virtual, m)
        # full fori_loop carry: three per-chunk rings + the two wire buffers
        live = virtual * (sch.ring_f + sch.ring_s + sch.ring_b) + 2
    bubble = round(bubble_fraction(n, m, virtual), 3)
    print(json.dumps({
        "schedule": schedule if virtual == 1 else f"1f1b@v{virtual}",
        "num_microbatches": m,
        "compile_s": round(compile_s, 2),
        "step_s": round(step_s, 4),
        "loss": round(float(loss), 4),
        "live_stage_inputs": live,
        "bubble_fraction": bubble,
    }), flush=True)


if __name__ == "__main__":
    for m in (4, 8, 16):
        for schedule in ("gpipe", "1f1b"):
            bench(schedule, m)
        if m % 4 == 0:  # interleaved needs m % pp == 0; 8 layers / (4*2) chunks
            bench("1f1b", m, virtual=2)
