"""A/B the fused flat-buffer train step vs the pytree step on the live chip.

Run alone (single-tenant chip). Prints one line per variant; the flat path
is the default whenever params are unpartitioned, so this doubles as the
regression probe for the per-buffer-overhead fix (utils/flatbuf.py).
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import time

import numpy as np

import jax
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.models.llama import (
    LlamaConfig,
    create_llama,
    llama_flops_per_token,
    llama_loss,
)
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState


def bench(label, flatten, steps=8, seq=2048, batch=8):
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=seq, remat_policy="minimal",
        attention_impl="flash", use_chunked_ce=True,
    )
    acc = Accelerator(mixed_precision="bf16")
    model, _ = acc.prepare(create_llama(cfg, seed=0), optax.adamw(3e-4, weight_decay=0.01))
    model.policy = None
    step = acc.train_step(
        llama_loss, max_grad_norm=1.0, multi_step=True, flatten_params=flatten
    )
    rng = np.random.default_rng(0)
    batches = {
        "input_ids": jax.device_put(
            rng.integers(0, 32000, size=(steps, batch, seq)).astype(np.int32)
        )
    }
    np.asarray(step(batches))  # compile + warm
    t0 = time.perf_counter()
    losses = step(batches)
    last = float(np.asarray(losses)[-1])
    dt = (time.perf_counter() - t0) / steps
    fl = llama_flops_per_token(cfg, seq) * batch * seq
    peak = 197e12
    print(
        f"{label}: {dt*1000:.0f}ms/step {batch*seq/dt:.0f} tok/s "
        f"mfu={fl/dt/peak*100:.1f}% loss={last:.3f}",
        flush=True,
    )
    del model, step, batches, losses
    acc.free_memory()
    jax.clear_caches()
    return dt


if __name__ == "__main__":
    bench("pytree  path", False)
    bench("flatbuf path", True)
    bench("flatbuf path (repeat)", True)
