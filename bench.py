"""Benchmark: Llama fused-train-step tokens/sec/chip on the local TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference publishes no tokens/sec for its FSDP2 benchmark (BASELINE.md),
so ``vs_baseline`` reports measured MFU / 0.45 (the north-star MFU floor).
Model size auto-scales to the chip's HBM; batch size backs off on OOM via
find_executable_batch_size.

The measurement needs a TPU. Without one, on a device kind that is not in the
peaks table, on a kernel that fails its check or on any other error, the
process exits non-zero and prints no number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np


METRIC = "llama_train_tokens_per_sec_per_chip"


def _run_child(timeout: float):
    """Run the measurement (``bench.py --child``) in a subprocess, so that the
    parent never touches JAX and never holds the chip. Returns the JSON dict
    the child printed, or None when it failed, timed out or printed none."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired as exc:
        err = exc.stderr or ""
        if isinstance(err, bytes):
            err = err.decode(errors="replace")
        sys.stderr.write(err[-4000:])
        sys.stderr.write(f"bench: the measurement did not end in {timeout:.0f} s\n")
        return None
    sys.stderr.write(out.stderr[-4000:])
    if out.returncode != 0:
        sys.stderr.write(f"bench: the measurement exited with {out.returncode}\n")
        return None
    return _last_result_line(out.stdout)


def _last_result_line(stdout: str):
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if parsed.get("metric") == METRIC and "value" in parsed:
                return parsed
    return None


# Dense bf16 peak FLOP/s of one chip, keyed by ``device.device_kind`` as JAX
# reports it. Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v4, v5e, v5p, v6e).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def detect_peak_flops(device) -> float:
    kind = getattr(device, "device_kind", None)
    if kind not in PEAK_FLOPS:
        raise ValueError(
            f"no peak FLOP/s known for device kind {kind!r} (platform "
            f"{getattr(device, 'platform', None)!r}); add it to bench.PEAK_FLOPS "
            "with its source"
        )
    return PEAK_FLOPS[kind]


def _measure(config, starting_batch, steps, seq_len):
    """Build a fresh accelerator+model for ``config``, run one fused
    multi-step program as warm-up and one timed, return the measurement."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models.llama import create_llama, llama_loss
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
    from accelerate_tpu.utils.memory import find_executable_batch_size

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    n_dev = len(jax.devices())
    pcfg = (
        ParallelismConfig(dp_shard_size=n_dev) if n_dev > 1 else ParallelismConfig()
    )
    accelerator = Accelerator(parallelism_config=pcfg, mixed_precision="bf16")
    model = create_llama(config, seed=0)
    # bf16 first moment (standard for large-model training) frees ~2 bytes/
    # param of HBM — the difference between the ~1B-param scale-phase
    # candidates fitting a 16 GB chip or RESOURCE_EXHAUSTED-ing
    mu_dtype = jnp.bfloat16 if os.environ.get("BENCH_MU_BF16", "1") == "1" else None
    model, _optimizer = accelerator.prepare(
        model, optax.adamw(3e-4, weight_decay=0.01, mu_dtype=mu_dtype)
    )
    model.policy = None  # model handles bf16 internally
    # all `steps` train steps fuse into ONE program (lax.scan)
    step_fn = accelerator.train_step(llama_loss, max_grad_norm=1.0, multi_step=True)
    rng = np.random.default_rng(0)

    @find_executable_batch_size(starting_batch_size=starting_batch)
    def run(batch_size):
        batches = {
            "input_ids": rng.integers(
                0, config.vocab_size, size=(steps, batch_size, seq_len)
            ).astype(np.int32)
        }
        device_batches = jax.device_put(batches)
        jax.block_until_ready(step_fn(device_batches))  # compile and warm up
        t0 = time.perf_counter()
        losses = jax.block_until_ready(step_fn(device_batches))
        dt = time.perf_counter() - t0
        return batch_size, dt, float(losses[-1])

    batch_size, dt, loss = run()
    tok_per_sec_per_chip = batch_size * seq_len * steps / dt / n_dev
    result = {
        "tok_s_chip": tok_per_sec_per_chip,
        "batch_size": batch_size,
        "step_time_s": dt / steps,
        "loss": loss,
        "params_m": model.num_parameters / 1e6,
        "n_devices": n_dev,
    }
    # free this candidate's HBM before the next one: the params + adam state
    # of a prior model otherwise survive via the jit executable cache, and
    # 4-5 sequential candidates exhaust a 16 GB chip (observed: every
    # full-steps re-measure RESOURCE_EXHAUSTED after the probe phase)
    del model, _optimizer, step_fn
    accelerator.free_memory()
    jax.clear_caches()
    return result


def relative_leaf_gate(cand_leaves, base_leaves, ref_leaves, labels, ratio=2.0):
    """Per-leaf relative numerics gate shared by the bench flash gate and
    ``benchmarks/kernel_validation.py`` (ONE implementation so the two can
    never drift): the candidate (bf16 kernel) must track the f32 reference
    within ``ratio``x of the bf16 baseline's own error, with a small
    absolute floor for near-zero baselines. Returns (ok, per-leaf dict)."""
    # a kernel variant silently dropping a grad leaf must FAIL the gate,
    # not shorten the zip and vacuously pass on the leaves that remain
    counts = {
        "labels": len(labels),
        "cand": len(cand_leaves),
        "base": len(base_leaves),
        "ref": len(ref_leaves),
    }
    if len(set(counts.values())) != 1:
        raise ValueError(f"relative_leaf_gate: leaf-count mismatch {counts}")
    ok = True
    details = {}
    for label, f, b, r in zip(labels, cand_leaves, base_leaves, ref_leaves):
        err_cand = float(np.abs(f - r).max())
        err_base = float(np.abs(b - r).max())
        floor = 1e-3 * max(1.0, float(np.abs(r).max()))
        passed = err_cand <= max(ratio * err_base, floor)
        details[label] = {
            "err_flash": round(err_cand, 6),
            "err_blockwise": round(err_base, 6),
            "pass": passed,
        }
        ok = ok and passed
    return ok, details


def _check_flash_on_device() -> None:
    """On-device fwd+bwd check of the Pallas flash kernel against the
    blockwise reference, at the tiling the benchmark runs. Raises when the
    kernel fails to compile, to run or to agree: a kernel that fails is an
    error, never a reason to measure another path in its place.

    The gate is RELATIVE: flash(bf16) must track an f32 blockwise reference
    about as well as blockwise(bf16) itself does (ratio <= 2, plus a small
    absolute floor for near-zero baselines). An absolute atol is wrong here:
    on a v5e flash dv missed a 5e-2 atol by exactly one bf16 quantum (0.0625)
    while matching the reference to bf16 round-off."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.ops.attention import blockwise_attention
    from accelerate_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    # validate at the tiling the benchmark actually runs (tall-q blocks at
    # the bench seq len) — a default-block check at seq 256 would never
    # exercise the block_q=2048 lowering the sweep measures
    seq = int(os.environ.get("BENCH_SEQ", 2048))
    blocks = dict(
        block_q=LlamaConfig.attention_block_q, block_k=LlamaConfig.attention_kv_block
    )
    shape = (2, seq, 8, 64)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16) for _ in range(3)
    )
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, **blocks).astype(jnp.float32)
        )

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True).astype(jnp.float32))

    def fetch(tree):
        return [np.asarray(t, np.float32) for t in jax.tree_util.tree_leaves(tree)]

    flash_all = fetch(
        jax.jit(
            lambda q, k, v: (
                flash_attention(q, k, v, causal=True, **blocks),
                jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v),
            )
        )(q, k, v)
    )
    blockwise = jax.jit(
        lambda q, k, v: (
            blockwise_attention(q, k, v, causal=True),
            jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v),
        )
    )
    base_all = fetch(blockwise(q, k, v))
    # f32 reference on the SAME inputs: the yardstick for bf16 round-off
    ref_all = fetch(blockwise(qf, kf, vf))
    ok, details = relative_leaf_gate(
        flash_all, base_all, ref_all, ("out", "dq", "dk", "dv")
    )
    if not ok:
        raise RuntimeError(f"bench: flash kernel disagrees with blockwise: {details}")


def main():
    import jax

    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.utils.environment import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU and JAX found {device.platform!r}; "
            "nothing was measured"
        )
    detect_peak_flops(device)  # an unknown chip is an error before any work
    seq_len = int(os.environ.get("BENCH_SEQ", 2048))

    def make_config(remat, attn, hidden=None, inter=None, layers=None):
        hidden = hidden or int(os.environ.get("BENCH_HIDDEN", 1024))
        return LlamaConfig(
            vocab_size=32000,
            hidden_size=hidden,
            intermediate_size=inter or int(os.environ.get("BENCH_INTER", int(hidden * 2.75))),
            num_hidden_layers=layers or int(os.environ.get("BENCH_LAYERS", 16)),
            num_attention_heads=max(hidden // 64, 1),
            num_key_value_heads=max(hidden // 64, 1),
            max_position_embeddings=seq_len,
            remat_policy=remat,
            attention_impl=attn,
            use_chunked_ce=os.environ.get("BENCH_CHUNKED_CE", "1") == "1",
        )

    starting_batch = int(os.environ.get("BENCH_BATCH", 8))
    steps = int(os.environ.get("BENCH_STEPS", 32))
    sweep = os.environ.get("BENCH_SWEEP", "1") == "1"
    default = (os.environ.get("BENCH_REMAT", "minimal"),
               os.environ.get("BENCH_ATTN", "blockwise"))
    candidates = [default]
    if sweep:
        for cand in (("dots", "blockwise"), ("nothing", "blockwise"),
                     (default[0], "flash")):
            if cand not in candidates:
                candidates.append(cand)
    if any(attn == "flash" for _, attn in candidates):
        # nothing flash-configured is timed unless the kernel is right here
        _check_flash_on_device()

    def _mfu(cfg, m):
        return _measured_mfu(device, cfg, seq_len, m)

    probed = []  # (probe_mfu, config, probe measurement)
    for remat, attn in candidates:
        cfg = make_config(remat, attn)
        m = _measure(cfg, starting_batch, steps=min(steps, 4), seq_len=seq_len)
        m.update(remat=remat, attention=attn)
        sys.stderr.write(
            f"bench: sweep {remat}/{attn}: {m['tok_s_chip']:.0f} tok/s/chip "
            f"mfu={_mfu(cfg, m):.3f}\n"
        )
        probed.append((_mfu(cfg, m), cfg, m))
    # phase 2: scale the model at the winning (remat, attn) — bigger
    # matmuls raise the MFU ceiling until HBM pushes the batch too low.
    # Gated on BENCH_SWEEP too: BENCH_SWEEP=0 means "measure exactly the
    # pinned config", which a model swap would silently violate.
    if sweep and os.environ.get("BENCH_SCALE_SWEEP", "1") == "1":
        top = max(probed, key=lambda t: t[0])[2]
        remat, attn = top["remat"], top["attention"]
        for hidden, inter, layers in ((2048, 5632, 16), (2560, 6912, 12)):
            cfg = make_config(remat, attn, hidden=hidden, inter=inter, layers=layers)
            try:
                m = _measure(cfg, starting_batch, steps=min(steps, 4), seq_len=seq_len)
            except Exception as exc:  # noqa: BLE001 — a size the chip cannot hold is a finding of the probe
                sys.stderr.write(f"bench: scale candidate {hidden} failed: {exc}\n")
                continue
            m.update(remat=remat, attention=attn)
            sys.stderr.write(
                f"bench: scale {hidden}x{layers}: {m['tok_s_chip']:.0f} tok/s/chip "
                f"mfu={_mfu(cfg, m):.3f}\n"
            )
            probed.append((_mfu(cfg, m), cfg, m))
    # the 4-step probes carry a fixed per-call dispatch cost that biases
    # MFU toward slower (bigger) configs — settle the top-2 at FULL steps
    probed.sort(key=lambda t: t[0], reverse=True)
    best = None
    for _, cfg, m in probed[:2]:
        full = _measure(cfg, m["batch_size"], steps=steps, seq_len=seq_len)
        full.update(remat=m["remat"], attention=m["attention"])
        sys.stderr.write(
            f"bench: final {full['remat']}/{full['attention']} "
            f"h={cfg.hidden_size}: {full['tok_s_chip']:.0f} tok/s/chip "
            f"mfu={_mfu(cfg, full):.3f}\n"
        )
        if best is None or _mfu(cfg, full) > _mfu(best[0], best[1]):
            best = (cfg, full)
    config, measured = best
    _emit(device, config, seq_len, measured)


def _measured_mfu(device, config, seq_len, measured) -> float:
    """The ranking metric and the reported `mfu` detail — ONE formula."""
    from accelerate_tpu.models.llama import llama_flops_per_token

    flops_per_token = llama_flops_per_token(config, seq_len)
    return (measured["tok_s_chip"] * flops_per_token) / detect_peak_flops(device)


def _emit(device, config, seq_len, measured):
    mfu = _measured_mfu(device, config, seq_len, measured)
    result = {
        "metric": METRIC,
        "value": round(measured["tok_s_chip"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "detail": {
            "platform": device.platform,
            "device": device.device_kind,
            "n_devices": measured["n_devices"],
            "batch_size": measured["batch_size"],
            "seq_len": seq_len,
            "params_m": round(measured["params_m"], 1),
            "step_time_s": round(measured["step_time_s"], 4),
            "mfu": round(mfu, 4),
            "loss": round(measured["loss"], 4),
            "remat": measured["remat"],
            "attention": measured["attention"],
        },
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if "--telemetry-gate" in sys.argv:
        # regression gate: async telemetry (fused health + async log) must
        # stay within 5% of telemetry-off steps/s on the CPU A/B
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.telemetry_bench import main as telemetry_main

        sys.exit(telemetry_main(gate=True))
    if "--recovery-gate" in sys.argv:
        # elastic-recovery gate: MTTR per restore path (local / replica /
        # elastic reshard) + consensus/replication steady-state overhead
        # must stay within 5% of replication-off steps/s
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.recovery_bench import main as recovery_main

        sys.exit(recovery_main(gate=True))
    if "--serving-gate" in sys.argv:
        # resilience gate: load ramp at 1x/2x/4x capacity + fault/recovery +
        # SIGTERM drain (docs/serving.md acceptance criteria)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.serving_bench import main as serving_main

        sys.exit(serving_main(gate=True))
    if "--fleet-gate" in sys.argv:
        # fleet gate: replica-ramp goodput scaling (>= 1.8x at 2x replicas),
        # kill-one-replica-mid-batch chaos with zero dropped futures, and
        # TTFT p99 no worse with prefill/decode disaggregation
        # (docs/serving.md acceptance criteria)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.serving_bench import fleet_main

        sys.exit(fleet_main(gate=True))
    if "--kernel-gate" in sys.argv:
        # kernel gate: every Pallas entry point — the flash-attention
        # variants plus the paged serving kernels (flash-decode, fused
        # verify, fused sampling epilogue) — must pass the shared
        # relative-leaf / exact-parity gates vs the reference ops.
        # Exit code = number of failing variants. On CPU the kernels run
        # in interpret mode (harness validation; see make check-kernels
        # for the committed artifact regen).
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.kernel_validation import main as kernel_main

        sys.exit(kernel_main())
    if "--kv-gate" in sys.argv:
        # paged KV-cache gate: >= 4x concurrent slots at fixed pool HBM with
        # bitwise dense parity + <= 2 engine programs, >= 90% shared-prefix
        # block dedup, deterministic int8 KV (docs/serving.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.continuous_bench import kv_main

        sys.exit(kv_main(gate=True))
    if "--spec-gate" in sys.argv:
        # speculative-decoding gate: >= 1.5x tokens/s on the repetitive-
        # suffix workload, bitwise parity + within-noise throughput on the
        # adversarial workload, <= 3 compiled engine programs, and dense-
        # vs-paged spec outputs bitwise identical (docs/serving.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.continuous_bench import spec_main

        sys.exit(spec_main(gate=True))
    if "--longctx-gate" in sys.argv:
        # long-context gate: a prompt >= 4x the single-shot prompt bucket
        # admitted via chunked prefill with bitwise greedy parity (dense +
        # paged), co-resident decode p99 <= 1.1x a short-only run, and the
        # host-RAM KV spill tier beating chunked prefix recompute at a
        # measured, reported crossover length (docs/serving.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.longctx_bench import main as longctx_main

        sys.exit(longctx_main(gate=True))
    if "--static-gate" in sys.argv:
        # graftcheck: static invariant analysis — host-lint rules G101-G105
        # plus AOT-lowered program checks G001-G004 against the committed
        # program/collective baseline (docs/static_analysis.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from accelerate_tpu.analysis.__main__ import main as static_main

        sys.exit(static_main([a for a in sys.argv[1:] if a != "--static-gate"]))
    if "--sharding-gate" in sys.argv:
        # graftcheck Level 3: static SPMD sharding & HBM audit — replicated
        # state, implicit reshards, per-program HBM budgets, DCN loop
        # collectives, missed donations (G201-G205) against
        # runs/sharding_baseline.json (docs/static_analysis.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from accelerate_tpu.analysis.__main__ import main as static_main

        sys.exit(static_main(
            ["--level", "sharding"]
            + [a for a in sys.argv[1:] if a != "--sharding-gate"]
        ))
    if "--concurrency-gate" in sys.argv:
        # graftcheck Level 4: host concurrency & gang-safety audit —
        # lock-order DAG vs runs/concurrency_baseline.json, blocking ops
        # under locks, cross-thread races, thread leaks, Future-resolution
        # discipline, gang-divergent collectives (G301-G306)
        # (docs/static_analysis.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from accelerate_tpu.analysis.__main__ import main as static_main

        sys.exit(static_main(
            ["--level", "concurrency"]
            + [a for a in sys.argv[1:] if a != "--concurrency-gate"]
        ))
    if "--numerics-gate" in sys.argv:
        # graftcheck Level 5: numerics, precision & RNG audit — f64/widened
        # aliases, accumulation-dtype discipline, state/scale dtype
        # contract, PRNG key reuse, non-determinism inventory, and the
        # bf16-vs-f32 drift witness vs runs/numerics_baseline.json
        # (docs/static_analysis.md); accepts --no-witness/--changed-only
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from accelerate_tpu.analysis.__main__ import main as static_main

        sys.exit(static_main(
            ["--level", "numerics"]
            + [a for a in sys.argv[1:] if a != "--numerics-gate"]
        ))
    if "--perf-gate" in sys.argv:
        # graftcheck Level 6: static performance audit — roofline
        # step-time/MFU/tokens-per-second budgets, unoverlapped-collective
        # detection, padding/bucket waste, fusion inventory, and pipeline
        # bubble budgets vs runs/perf_baseline.json, plus the
        # predicted-vs-measured ordering witness (G501-G505)
        # (docs/static_analysis.md); accepts --no-witness/--changed-only
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from accelerate_tpu.analysis.__main__ import main as static_main

        sys.exit(static_main(
            ["--level", "perf"]
            + [a for a in sys.argv[1:] if a != "--perf-gate"]
        ))
    if "--obs-gate" in sys.argv:
        # perf-observatory gate: observatory-on serving goodput >= 0.98x
        # off (timers + live /metrics scraping), scrape p99 under budget,
        # and the drift-sentinel chaos probe — a fault-injected slowdown
        # must raise exactly one typed PerfDriftError and exactly one
        # budgeted drift dump (docs/observability.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.obs_bench import main as obs_main

        sys.exit(obs_main(gate=True))
    if "--controller-gate" in sys.argv:
        # self-healing fleet gate: SLO controller vs static peak under the
        # seeded ramp/flash-crowd/drain replay (TTFT p99 within SLO with
        # fewer replica-seconds), drift-finding replica replacement, and
        # fail-static freeze with exactly one typed ControllerStaleError
        # (docs/control_plane.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.autoscale_bench import main as autoscale_main

        sys.exit(autoscale_main(gate=True))
    if "--chaos-gate" in sys.argv:
        # gray-failure gate: seeded chaos conductor (10x straggler, flaky
        # probe hops, one kill-mid-batch) vs a no-chaos run of the same
        # arrivals — goodput >= 0.85x, TTFT p99 <= 1.5x, zero dropped
        # futures, invariant monitors clean, brown-out quarantine +
        # drain-and-replace observed, and a bit-identical firing-sequence
        # replay (docs/fault_tolerance.md)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.chaos_bench import main as chaos_main

        sys.exit(chaos_main(gate=True))
    if "--continuous-gate" in sys.argv:
        # continuous-batching gate: mixed-length/mixed-budget workload must
        # reach >= 1.3x static-mode goodput with TTFT p99 no worse, <= 2
        # compiled engine programs, and greedy output parity
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.continuous_bench import main as continuous_main

        sys.exit(continuous_main(gate=True))
    if "--child" in sys.argv:
        # the measurement itself: whatever it raises ends the process non-zero
        main()
        sys.exit(0)

    # Parent: stays off JAX so that the child is the one process on the chip.
    # No result, for whatever reason, is a failure and prints no number.
    result = _run_child(float(os.environ.get("BENCH_TPU_TIMEOUT", 1800)))
    if result is None:
        sys.exit(1)
    print(json.dumps(result), flush=True)
