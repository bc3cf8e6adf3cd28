# Test shards mirroring the reference's Makefile:18-56.
#
# `make test`     — CI-sized default (~7 min): graftcheck + the Pallas
#                   kernel-validation suite, then the fast pytest shard;
#                   slow-marked compile-heavy integration tests are skipped
#                   (RUN_SLOW gate, the reference's slow-test convention).
# `make test_all` — the FULL suite (incl. slow) in documented shards; total
#                   ~18 min of mostly jit compile time on the 8-dev CPU mesh.
PY := env JAX_PLATFORMS=cpu python
PY_SLOW := env JAX_PLATFORMS=cpu RUN_SLOW=1 python

.PHONY: test test_all test_core test_data test_parallel test_models test_cli test_big_modeling test-fault test-serving check-static check-kernels check-sharding check-concurrency check-numerics check-perf check-all install-hooks bench bench-telemetry bench-serving bench-continuous bench-recovery bench-kv bench-spec bench-fleet bench-trace bench-obs bench-autoscale bench-chaos bench-longctx

test: check-static check-kernels
	$(PY) -m pytest tests/ -q

# CPU interpret-mode validation of EVERY Pallas kernel entry point (flash
# variants + the paged flash-decode / fused-verify / fused-sampling serving
# kernels) against their reference ops, regenerating the committed artifact
# write-to-temp + rename so a failing run never clobbers the last good one.
# Same suite as `python bench.py --kernel-gate` (which prints to stdout
# without touching the artifact).
check-kernels:
	$(PY) benchmarks/kernel_validation.py > runs/kernel_validation_cpu_interpret.jsonl.tmp
	mv runs/kernel_validation_cpu_interpret.jsonl.tmp runs/kernel_validation_cpu_interpret.jsonl

# graftcheck: static invariant analysis (docs/static_analysis.md).
# Level 1 AOT-lowers the registered hot programs (fused train step, engine
# prefill/decode/verify per backend) and checks callbacks, donation
# aliasing, weak types, and program/collective budgets against
# runs/static_baseline.json; Level 2 is the host AST lint (G101-G105);
# Level 3 audits SPMD shardings + static HBM budgets (G201-G205) against
# runs/sharding_baseline.json; Level 4 audits host concurrency & gang
# safety (G301-G306) against the lock-order DAG in
# runs/concurrency_baseline.json; Level 5 audits numerics/precision/RNG
# discipline (G401-G405) and runs the bf16-vs-f32 drift witness against
# runs/numerics_baseline.json; Level 6 audits static performance —
# roofline step-time/MFU/tok-s budgets, unoverlapped collectives, padding
# waste, fusion inventory, pipeline bubbles (G501-G505) — against
# runs/perf_baseline.json with a predicted-vs-measured ordering witness.
# check-static runs ALL levels; exit 0 = clean. Re-baseline deliberate
# program/budget/lock-order/drift/perf changes atomically (all five
# baseline files, write-to-temp + rename) with:
#   $(PY) -m accelerate_tpu.analysis --update-baseline
check-static:
	$(PY) -m accelerate_tpu.analysis

# Level 3 alone: replicated-state, implicit-reshard, HBM-budget, DCN-loop,
# and missed-donation audit of the lowered hot programs across the
# parallelism variants (dp8 / fsdp8 / tp2 / hsdp2x4 + engine backends)
check-sharding:
	$(PY) -m accelerate_tpu.analysis --level sharding

# Level 4 alone: host concurrency & gang-safety audit of the threaded
# modules (serving/fleet/elastic/engine/telemetry/state/data_loader) —
# lock-order DAG vs runs/concurrency_baseline.json, blocking-under-lock,
# cross-thread races, thread leaks, Future-resolution discipline, and
# gang-divergent collectives (G301-G306). Pure AST: no jax import, <1s.
check-concurrency:
	$(PY) -m accelerate_tpu.analysis --level concurrency

# Level 5 alone: numerics, precision & RNG audit (G401-G405) — f64/widened
# aliases, accumulation-dtype discipline, state/scale dtype contract, PRNG
# key reuse, non-determinism inventory, plus the bf16-vs-f32 drift witness
# gated against runs/numerics_baseline.json. Pre-commit fast path:
#   $(PY) -m accelerate_tpu.analysis --level numerics --changed-only
check-numerics:
	$(PY) -m accelerate_tpu.analysis --level numerics

# Level 6 alone: static performance audit (G501-G505) — per-program
# roofline step-time/MFU/tokens-per-second budgets, unoverlapped or
# DCN-unhideable collectives, padding/bucket dot-FLOP waste, fusion/kernel
# inventory, and pipeline bubble-fraction budgets vs
# runs/perf_baseline.json, plus the predicted-vs-measured A/B ordering
# witness (paged-vs-dense decode, dp8-vs-fsdp8 train)
check-perf:
	$(PY) -m accelerate_tpu.analysis --level perf

# every level (1-6) + a SARIF report CI can annotate PRs from
check-all:
	$(PY) -m accelerate_tpu.analysis --level all --sarif runs/graftcheck.sarif

# install the graftcheck pre-commit hook: the --changed-only fast path
# (<30s — only the program groups whose sources differ from the
# merge-base are re-lowered; witnesses skipped) + a SARIF report
install-hooks:
	install -m 0755 scripts/pre-commit .git/hooks/pre-commit
	@echo "installed .git/hooks/pre-commit (graftcheck --changed-only)"

# durable-checkpointing suite (docs/fault_tolerance.md): atomic commit,
# kill-mid-save rollback via ACCELERATE_TPU_FAULT_INJECT, preemption,
# health watchdog, supervisor backoff/crash-loop, plus the elastic layer —
# replication kill points, consensus, replica restore, topology-change
# resume — fast, on 8 virtual CPU devices (XLA_FLAGS from tests/conftest.py)
test-fault:
	$(PY) -m pytest tests/test_durability.py tests/test_checkpointing.py tests/test_serving.py tests/test_elastic.py tests/test_fleet.py tests/test_chaos.py -q
	$(PY) benchmarks/chaos_bench.py --gate

# resilient-serving suite (docs/serving.md): dynamic batching, deadline
# shedding, backpressure, retry/backoff, circuit breaker, SIGTERM drain,
# fault-injected batch death (exactly-once replies), plus the continuous-
# batching engine (slot lifecycle, seed reproducibility, mode parity) and
# the paged KV-cache subsystem (block tables, COW prefix cache, int8 KV)
test-serving:
	$(PY) -m pytest tests/test_serving.py tests/test_engine.py tests/test_kvcache.py tests/test_spec.py tests/test_fleet.py -q

test_all:
	$(PY_SLOW) -m pytest tests/test_state.py tests/test_operations.py tests/test_parallelism_config.py tests/test_accelerator.py tests/test_checkpointing.py tests/test_tracking.py tests/test_data_loader.py tests/test_data_shard_info.py tests/test_misc.py tests/test_cli.py tests/test_big_modeling.py tests/test_losses.py tests/test_flatbuf.py tests/test_local_sgd.py tests/test_api_parity.py tests/test_hlo_analysis.py tests/test_tracking_fakes.py tests/test_powersgd.py -q
	$(PY_SLOW) -m pytest tests/test_llama.py tests/test_gpt2.py tests/test_bert.py tests/test_t5.py tests/test_resnet.py tests/test_attention.py tests/test_flash_attention.py tests/test_fp8_quantization.py tests/test_native_packing.py tests/test_interop.py -q
	$(PY_SLOW) -m pytest tests/test_context_parallel.py tests/test_pipeline.py tests/test_moe.py tests/test_composition.py tests/test_inference.py -q
	$(PY_SLOW) -m pytest tests/test_multiprocess.py tests/test_examples.py tests/test_fault_tolerance.py -q

test_core:
	$(PY) -m pytest tests/test_state.py tests/test_operations.py tests/test_parallelism_config.py tests/test_accelerator.py tests/test_checkpointing.py tests/test_tracking.py -q

test_data:
	$(PY) -m pytest tests/test_data_loader.py -q

test_parallel:
	$(PY) -m pytest tests/test_context_parallel.py tests/test_pipeline.py tests/test_moe.py -q

test_models:
	$(PY) -m pytest tests/test_llama.py tests/test_bert.py tests/test_attention.py tests/test_flash_attention.py -q

test_cli:
	$(PY) -m pytest tests/test_cli.py -q

test_big_modeling:
	$(PY) -m pytest tests/test_big_modeling.py -q

bench:
	python bench.py

# CPU A/B regression gate: fused health + async logging must stay within
# 5% of telemetry-off steps/s (docs/fault_tolerance.md)
bench-telemetry:
	$(PY) benchmarks/telemetry_bench.py --gate

# serving resilience gate: load ramp at 1x/2x/4x capacity, breaker
# open/close under injected faults, recovery throughput >= 95% of
# baseline, SIGTERM drain exits 143 with zero dropped in-flight
# (docs/serving.md)
bench-serving:
	$(PY) benchmarks/serving_bench.py --gate

# continuous-batching gate: mixed-length/mixed-budget greedy workload,
# continuous mode >= 1.3x static goodput with TTFT p99 no worse, exactly
# two compiled engine programs, bitwise output parity (docs/serving.md)
bench-continuous:
	$(PY) benchmarks/continuous_bench.py --gate

# paged KV-cache gate: a paged engine must admit >= 4x the concurrent slots
# of dense at ~equal pool HBM with bitwise greedy parity and <= 2 compiled
# programs; COW prefix caching must dedup >= 90% of shared-system-prompt
# blocks; int8 KV must be bitwise run-to-run deterministic (docs/serving.md)
bench-kv:
	$(PY) benchmarks/continuous_bench.py --kv-gate

# speculative-decoding gate: prompt-lookup drafts + fused verify must reach
# >= 1.5x plain continuous tokens/s on the repetitive-suffix workload with
# bitwise greedy parity, stay within noise + bitwise identical on the
# adversarial incompressible workload, keep <= 3 compiled engine programs,
# and match dense-vs-paged spec outputs bitwise (docs/serving.md)
bench-spec:
	$(PY) benchmarks/continuous_bench.py --spec-gate

# fleet gate: replica-ramp goodput scaling (>= 1.8x goodput at 2x
# replicas), kill-one-replica-mid-batch chaos with zero dropped futures
# (typed errors or completions only, failover observed), and TTFT p99 no
# worse with prefill/decode disaggregation than without (docs/serving.md);
# --cross-replica adds the wire KV-transfer phase: remote prefill over TCP
# loopback must hold TTFT p99 <= 1.3x the in-process hand-off, with the
# cross-replica prefix hit rate reported (docs/serving.md "Cross-host
# disaggregated prefill")
bench-fleet:
	$(PY) benchmarks/serving_bench.py --fleet-gate --cross-replica

# long-context gate: a prompt >= 4x the single-shot prompt bucket admitted
# via chunked prefill with bitwise greedy parity vs single-shot (dense +
# paged), co-resident decode p99 <= 1.1x a short-only run, and the host-RAM
# KV spill tier beating chunked prefix recompute at a measured, reported
# crossover length (docs/serving.md "Long-context serving")
bench-longctx:
	$(PY) benchmarks/longctx_bench.py --gate

# tracing gate: span-spine overhead (tracing-on serving goodput >= 0.98x
# off) + flight-recorder chaos forensics — kill a replica mid-batch and the
# dump must show, per affected request, the failed dispatch span, a typed
# error event, and the successful failover dispatch, with zero dropped
# futures and zero dropped spans (docs/observability.md)
bench-trace:
	$(PY) benchmarks/tracing_bench.py --gate

# perf-observatory gate: observatory-on serving goodput >= 0.98x off with a
# live /metrics scraper attached, scrape p99 under 50ms against a loaded
# server, and drift-sentinel chaos — a fault-injected slowdown raises
# exactly one typed PerfDriftError + one budgeted drift dump
# (docs/observability.md)
bench-obs:
	$(PY) benchmarks/obs_bench.py --gate

# self-healing fleet gate: under the seeded ramp + flash-crowd + drain
# replay the SLO controller must hold TTFT p99 within the SLO with
# measurably fewer replica-seconds than static peak provisioning (both
# reported), replace exactly one replica on an injected perf-drift
# finding, and fail static (frozen actuation + exactly one typed
# ControllerStaleError) on a blinded observe path — zero dropped futures
# throughout (docs/control_plane.md)
bench-autoscale:
	$(PY) benchmarks/autoscale_bench.py --gate

# gray-failure gate: one seeded chaos schedule (10x straggler + flaky=0.2
# probe hops + one kill-mid-batch) against the load replay, invariant
# monitors armed throughout — goodput >= 0.85x and TTFT p99 <= 1.5x of the
# no-chaos run, zero dropped futures / untyped errors, complete trace
# trees, the browned-out replica quarantined then drained-and-replaced
# automatically, and the recorded hit log replaying to a bit-identical
# firing sequence (docs/fault_tolerance.md)
bench-chaos:
	$(PY) benchmarks/chaos_bench.py --gate

# elastic-recovery gate: MTTR per restore path (local / replica / elastic
# reshard, restart-to-resumed wall clock) + consensus/replication must stay
# within 5% of replication-off steps/s (docs/fault_tolerance.md)
bench-recovery:
	$(PY) benchmarks/recovery_bench.py --gate
