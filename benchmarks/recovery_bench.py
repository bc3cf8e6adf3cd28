"""Recovery bench: MTTR for the three restore paths + the elastic-recovery
steady-state overhead gate.

Two measurements (docs/fault_tolerance.md "Replication & elastic resume"):

* **MTTR** — wall-clock from process start to ``resumed=True`` for each
  recovery path, measured as real restarts (fresh interpreter + jax init +
  restore) of ``test_utils/scripts/elastic_recovery_script.py``:

  - ``local``   — the committed local tree is intact (the common restart)
  - ``replica`` — the local tree was wiped; restore pulls a
                  checksum-verified replica back first
  - ``elastic`` — the restored checkpoint was written on an 8-device mesh
                  and is resharded onto a 4-device mesh (``elastic=True``)

* **Steady-state overhead** — the same train loop with periodic
  ``save_state`` timed with replication off vs async replication on. The
  consensus/replication machinery must cost < 5% steps/s (``--gate`` /
  ``make bench-recovery`` / ``bench.py --recovery-gate`` fail below
  ``RB_GATE_RATIO``, default 0.95).

Prints one JSON line per measurement plus a gate line.
"""

from __future__ import annotations

import os
import sys as _sys

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import json
import shutil
import subprocess
import tempfile
import time

import numpy as np

HIDDEN = int(os.environ.get("RB_HIDDEN", "768"))
BATCH = int(os.environ.get("RB_BATCH", "128"))
STEPS = int(os.environ.get("RB_STEPS", "60"))
SAVE_EVERY = int(os.environ.get("RB_SAVE_EVERY", "20"))
WARMUP = int(os.environ.get("RB_WARMUP", "10"))
REPEATS = int(os.environ.get("RB_REPEATS", "2"))
GATE_RATIO = float(os.environ.get("RB_GATE_RATIO", "0.95"))

SCRIPT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "accelerate_tpu", "test_utils", "scripts", "elastic_recovery_script.py",
)


# ------------------------------------------------------- steady-state overhead
def _run_mode(mode: str, workdir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.model import Model
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.dataclasses import ReplicationConfig

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

    project = os.path.join(workdir, f"proj_{mode}")
    replication = None
    if mode == "replicated":
        replication = ReplicationConfig(
            target=os.path.join(workdir, f"replica_{mode}"), keep=2
        )
    acc = Accelerator(project_dir=project, replication_config=replication)
    acc.project_configuration.automatic_checkpoint_naming = True
    acc.project_configuration.total_limit = 2

    model, opt = acc.prepare(Model(apply_fn, params), optax.adamw(1e-3))
    step_fn = acc.train_step(loss_fn)
    batch = jax.device_put({"x": x, "y": y})

    loss = None
    for _ in range(WARMUP):
        loss = step_fn(batch)
    jax.block_until_ready(loss)
    acc.save_state()  # compile/warm the save path outside the timed region

    t0 = time.perf_counter()
    for i in range(STEPS):
        loss = step_fn(batch)
        if (i + 1) % SAVE_EVERY == 0:
            acc.save_state()
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    acc.end_training()  # drains the replicator OUTSIDE the timed loop
    shutil.rmtree(project, ignore_errors=True)
    return {
        "mode": mode,
        "steps_per_s": round(STEPS / dt, 1),
        "total_s": round(dt, 4),
        "steps": STEPS,
        "saves": STEPS // SAVE_EVERY,
        "final_loss": round(float(np.asarray(loss)), 5),
    }


def _best_of(mode: str, workdir: str, repeats: int) -> dict:
    best = None
    for _ in range(repeats):
        row = _run_mode(mode, workdir)
        if best is None or row["steps_per_s"] > best["steps_per_s"]:
            best = row
    return best


# ------------------------------------------------------------------------ MTTR
def _script_env(device_count: int, replica: str) -> dict:
    env = dict(os.environ)
    env.pop("ACCELERATE_TPU_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
    env["PYTHONPATH"] = os.path.dirname(SCRIPT.rsplit("accelerate_tpu", 1)[0])
    env["ACCELERATE_REPLICATION_TARGET"] = replica
    env["ACCELERATE_REPLICATION_SYNC"] = "1"
    return env


def _timed_restart(label: str, argv: list, env: dict) -> dict:
    t0 = time.perf_counter()
    run = subprocess.run(
        [_sys.executable, SCRIPT, *argv],
        env=env, capture_output=True, text=True, timeout=600,
    )
    dt = time.perf_counter() - t0
    ok = run.returncode == 0 and "resumed=True" in run.stdout
    if not ok:
        _sys.stderr.write(
            f"recovery_bench: {label} restart failed rc={run.returncode}\n"
            f"{run.stderr[-2000:]}\n"
        )
    return {
        "mode": f"mttr_{label}",
        "restart_to_resumed_s": round(dt, 2),
        "ok": ok,
    }


def _mttr(workdir: str) -> list:
    project = os.path.join(workdir, "mttr_proj")
    replica = os.path.join(workdir, "mttr_replica")
    ref = os.path.join(workdir, "mttr_ref")
    env = _script_env(8, replica)
    train = subprocess.run(
        [_sys.executable, SCRIPT, "--phase", "train",
         "--project_dir", project, "--ref_out", ref],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if train.returncode != 0:
        _sys.stderr.write(
            f"recovery_bench: MTTR prep failed rc={train.returncode}\n"
            f"{train.stderr[-2000:]}\n"
        )
        return []
    got = os.path.join(workdir, "mttr_got.npy")
    rows = []

    # the common restart: local tree intact
    rows.append(_timed_restart(
        "local",
        ["--phase", "verify", "--project_dir", project, "--ref_out", got],
        env,
    ))
    # host-loss restart: local tree wiped, replica restore first
    shutil.rmtree(os.path.join(project, "checkpoints"), ignore_errors=True)
    rows.append(_timed_restart(
        "replica",
        ["--phase", "verify", "--project_dir", project, "--ref_out", got],
        env,
    ))
    # world-change restart: the 8-device checkpoint reshards onto 4 devices
    rows.append(_timed_restart(
        "elastic",
        ["--phase", "verify", "--project_dir", project, "--ref_out", got,
         "--elastic"],
        _script_env(4, replica),
    ))
    return rows


def main(gate: bool = False) -> int:
    workdir = tempfile.mkdtemp(prefix="recovery_bench_")
    try:
        for row in _mttr(workdir):
            print(json.dumps(row), flush=True)

        rows = {}
        for mode in ("off", "replicated"):
            rows[mode] = _best_of(mode, workdir, REPEATS)
            print(json.dumps(rows[mode]), flush=True)
        ratio = rows["replicated"]["steps_per_s"] / rows["off"]["steps_per_s"]
        ok = ratio >= GATE_RATIO
        print(json.dumps({
            "metric": "recovery_overhead_gate",
            "replicated_vs_off": round(ratio, 3),
            "threshold": GATE_RATIO,
            "pass": ok,
            "note": "replicated = async checkpoint replication riding the "
                    "same periodic-save train loop; MTTR lines above are "
                    "restart-to-resumed wall clock per recovery path",
        }), flush=True)
        return 0 if (ok or not gate) else 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main(gate="--gate" in _sys.argv))
