"""Same-host A/B: per-step framework overhead vs the reference accelerate.

Both frameworks are installed in this image, so this is a directly
re-runnable head-to-head on identical hardware (CPU): the same tiny
2-layer MLP regression task, same batch size, same AdamW math, N
optimizer steps through each framework's idiomatic loop —

- reference: ``accelerate.Accelerator`` + torch DataLoader + eager
  backward/step (its design: per-step Python, hooks, autograd graph)
- ours: ``accelerate_tpu.Accelerator`` + the fused ``train_step``
  (its design: forward+backward+update+schedule compiled into ONE XLA
  program; ``multi_step=True`` folds the whole epoch into one dispatch)

At tiny model sizes compute is negligible, so steps/s measures the
per-step host overhead each framework imposes — the quantity that caps
small-model/step-frequency workloads. This is NOT a TPU compute claim
(no speed of this repository has been measured on a TPU yet: PERF.md);
it isolates the framework-design term on hardware anyone can rerun.

Prints one JSON line per framework plus a ratio line.
"""

from __future__ import annotations

import os
import sys as _sys

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import json
import time

import numpy as np

HIDDEN = int(os.environ.get("AB_HIDDEN", "256"))
BATCH = int(os.environ.get("AB_BATCH", "32"))
N_SAMPLES = 2048  # one epoch = 2048/BATCH steps
LR = 1e-3


def _data(seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(HIDDEN, 1)).astype(np.float32)
    x = rng.normal(size=(N_SAMPLES, HIDDEN)).astype(np.float32)
    y = np.tanh(x @ w) + 0.01 * rng.normal(size=(N_SAMPLES, 1)).astype(np.float32)
    return x, y.astype(np.float32)


def run_reference(epochs):
    import torch
    from accelerate import Accelerator

    torch.manual_seed(0)
    x, y = _data()
    ds = torch.utils.data.TensorDataset(torch.from_numpy(x), torch.from_numpy(y))
    loader = torch.utils.data.DataLoader(ds, batch_size=BATCH, shuffle=False)
    model = torch.nn.Sequential(
        torch.nn.Linear(HIDDEN, HIDDEN), torch.nn.Tanh(),
        torch.nn.Linear(HIDDEN, 1),
    )
    opt = torch.optim.AdamW(model.parameters(), lr=LR)
    accelerator = Accelerator()
    model, opt, loader = accelerator.prepare(model, opt, loader)

    def epoch():
        last = None
        for xb, yb in loader:
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(xb), yb)
            accelerator.backward(loss)
            opt.step()
            last = loss
        return float(last.detach())

    loss = epoch()  # warmup (allocator, autograd caches)
    t0 = time.perf_counter()
    for _ in range(epochs):
        loss = epoch()
    dt = time.perf_counter() - t0
    steps = epochs * (N_SAMPLES // BATCH)
    return {"framework": "accelerate(torch,cpu)", "steps_per_s": round(steps / dt, 1),
            "total_s": round(dt, 3), "steps": steps, "final_loss": round(loss, 5)}


def run_ours(epochs):
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.model import Model
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    x, y = _data()
    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(HIDDEN, HIDDEN)) * 0.06, jnp.float32),
        "b1": jnp.zeros((HIDDEN,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(HIDDEN, 1)) * 0.06, jnp.float32),
        "b2": jnp.zeros((1,), jnp.float32),
    }

    def apply_fn(p, xb):
        return jnp.tanh(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def loss_fn(model_view, batch):
        pred = model_view(batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    accelerator = Accelerator()
    model, opt = accelerator.prepare(
        Model(apply_fn, params), optax.adamw(LR)
    )
    step_fn = accelerator.train_step(loss_fn, multi_step=True)

    n_steps = N_SAMPLES // BATCH
    batches = {
        "x": x[: n_steps * BATCH].reshape(n_steps, BATCH, HIDDEN),
        "y": y[: n_steps * BATCH].reshape(n_steps, BATCH, 1),
    }
    device_batches = jax.device_put(batches)
    losses = step_fn(device_batches)  # warmup: compile
    _ = np.asarray(losses)
    t0 = time.perf_counter()
    for _ in range(epochs):
        losses = step_fn(device_batches)
    loss = float(np.asarray(losses)[-1])  # fetch forces completion
    dt = time.perf_counter() - t0
    steps = epochs * n_steps
    return {"framework": "accelerate_tpu(xla,cpu)", "steps_per_s": round(steps / dt, 1),
            "total_s": round(dt, 3), "steps": steps, "final_loss": round(loss, 5)}


def main():
    epochs = int(os.environ.get("AB_EPOCHS", "5"))
    ref = run_reference(epochs)
    print(json.dumps(ref), flush=True)
    ours = run_ours(epochs)
    print(json.dumps(ours), flush=True)
    print(json.dumps({
        "metric": "per_step_overhead_ratio",
        "value": round(ours["steps_per_s"] / ref["steps_per_s"], 2),
        "unit": "x reference steps/s (same tiny MLP, same host, CPU)",
        "note": "framework per-step overhead comparison on the CPU; says "
                "nothing about TPU speed",
    }), flush=True)


if __name__ == "__main__":
    main()
