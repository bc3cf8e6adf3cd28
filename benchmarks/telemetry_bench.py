"""Telemetry overhead A/B: fused health checks + async logging vs nothing.

PR 1's watchdog and per-step ``log()`` were host sync points — every call
flushed the async dispatch pipeline (`benchmarks/overhead_ab.py` measures
what that pipeline is worth at the pure-overhead limit). This bench pins
the claim that the non-blocking telemetry path costs ~nothing: the same
tiny-MLP fused train_step loop is timed three ways on CPU —

- ``off``    — no health check, no logging (the floor)
- ``sync``   — PR 1 shape: per-step sync health verdict + sync JSONL log
- ``async``  — deferred-readback ring health + async tracker flusher

and the regression gate (``--gate`` / ``make bench-telemetry`` /
``bench.py --telemetry-gate``) fails when async drops below 95% of off.

Prints one JSON line per mode plus a gate line.
"""

from __future__ import annotations

import os
import sys as _sys

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import json
import shutil
import tempfile
import time

import numpy as np

# step time ~8 ms on CPU at these shapes — the ms-scale regime the telemetry
# is built for (TPU steps). At pure-overhead scale (HIDDEN=256: ~0.6 ms) any
# extra per-step XLA dispatch is a visible fraction and the gate measures
# dispatch jitter, not telemetry design; benchmarks/overhead_ab.py measures
# the pure-overhead regime.
HIDDEN = int(os.environ.get("TB_HIDDEN", "768"))
BATCH = int(os.environ.get("TB_BATCH", "128"))
STEPS = int(os.environ.get("TB_STEPS", "200"))
WARMUP = int(os.environ.get("TB_WARMUP", "20"))
REPEATS = int(os.environ.get("TB_REPEATS", "3"))
GATE_RATIO = float(os.environ.get("TB_GATE_RATIO", "0.95"))
LR = 1e-3


def _run_mode(mode: str, workdir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.model import Model
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import TrainingHealthConfig

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()

    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(HIDDEN, HIDDEN)) * 0.06, jnp.float32),
        "b1": jnp.zeros((HIDDEN,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(HIDDEN, 1)) * 0.06, jnp.float32),
        "b2": jnp.zeros((1,), jnp.float32),
    }
    x = rng.normal(size=(BATCH, HIDDEN)).astype(np.float32)
    y = np.tanh(x[:, :1]).astype(np.float32)

    def apply_fn(p, xb):
        return jnp.tanh(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def loss_fn(model_view, batch):
        return jnp.mean((model_view(batch["x"]) - batch["y"]) ** 2)

    if mode == "off":
        acc = Accelerator()
    elif mode == "sync":
        acc = Accelerator(
            project_dir=workdir,
            log_with="jsonl",
            health_config=TrainingHealthConfig(sync=True),
        )
    elif mode == "async":
        acc = Accelerator(
            project_dir=workdir,
            log_with="jsonl",
            health_config=TrainingHealthConfig(sync=False, readback_depth=2),
            async_logging=True,
        )
    else:
        raise ValueError(mode)

    model, opt = acc.prepare(Model(apply_fn, params), optax.adamw(LR))
    step_fn = acc.train_step(loss_fn)
    if mode != "off":
        acc.init_trackers(f"telemetry_bench_{mode}")
    batch = jax.device_put({"x": x, "y": y})

    def one_step(i):
        loss = step_fn(batch)
        if mode != "off":
            acc.check_step_health(loss=loss)
            acc.log({"loss": loss}, step=i)
        return loss

    for i in range(WARMUP):
        one_step(i)
    jax.block_until_ready(model.params)

    t0 = time.perf_counter()
    loss = None
    for i in range(STEPS):
        loss = one_step(i)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    if mode != "off":
        acc.end_training()
    return {
        "mode": mode,
        "steps_per_s": round(STEPS / dt, 1),
        "total_s": round(dt, 4),
        "steps": STEPS,
        "final_loss": round(float(np.asarray(loss)), 5),
    }


def _best_of(mode: str, workdir: str, repeats: int) -> dict:
    # best-of-N: telemetry overhead is an additive per-step cost, so the
    # fastest repeat is the least-noisy estimate of each mode's floor
    best = None
    for _ in range(repeats):
        row = _run_mode(mode, workdir)
        if best is None or row["steps_per_s"] > best["steps_per_s"]:
            best = row
    return best


def main(gate: bool = False) -> int:
    workdir = tempfile.mkdtemp(prefix="telemetry_bench_")
    try:
        rows = {}
        for mode in ("off", "sync", "async"):
            rows[mode] = _best_of(mode, workdir, REPEATS)
            print(json.dumps(rows[mode]), flush=True)
        ratio_async = rows["async"]["steps_per_s"] / rows["off"]["steps_per_s"]
        ratio_sync = rows["sync"]["steps_per_s"] / rows["off"]["steps_per_s"]
        ok = ratio_async >= GATE_RATIO
        print(json.dumps({
            "metric": "telemetry_overhead_gate",
            "async_vs_off": round(ratio_async, 3),
            "sync_vs_off": round(ratio_sync, 3),
            "threshold": GATE_RATIO,
            "pass": ok,
            "note": "async = deferred-ring health + async tracker flush; "
                    "sync = PR1-shape per-step readback",
        }), flush=True)
        return 0 if (ok or not gate) else 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main(gate="--gate" in _sys.argv))
