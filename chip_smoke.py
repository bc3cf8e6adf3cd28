"""Prove on the chip that the trainer and the server start, compile and answer right.

``python chip_smoke.py`` needs one TPU chip and runs, in one process:

* *train*: a Mistral-7B-width llama (published widths, depth cut to what 16 GB
  holds with AdamW state) through ``Accelerator.prepare`` →
  ``prepare_data_loader`` → ``train_step`` with the Pallas flash kernel;
* *kernels*: ``paged_flash_decode`` / ``paged_flash_verify`` / ``fused_sample``
  against the reference ops they replace, at GPT-2-large and Mistral widths;
* *serve*: GPT-2 large, whole, through ``InferenceServer`` with the paged cache
  and ``attention_impl="pallas"``.

``python chip_smoke.py --chips 4`` needs the four chips of one host and runs
only the sharded trainer and what it is compared with.

Every phase prints one JSON line of facts. Nothing here is a benchmark number.
The last line of a run that passed is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a run that failed raises, exits non-zero and does not print it. There is no CPU
branch: without a TPU the script says so and exits 2. The phases are plain
functions of a config so that ``tests/test_chip_smoke.py`` can call them at tiny
sizes on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import re
import sys
import time

import numpy as np

# AdamW without warm-up moves every weight by about the learning rate in each
# of its first steps. On the chip 1e-3 sent the 4096-wide model from 7.8 to
# 16.2 on its sixth step; 1e-4 falls steadily at both the real and the tiny width.
LEARNING_RATE = 1e-4

# Flash and blockwise attention compute the same softmax in bf16 with f32
# accumulators over different tile orders, so each attention output differs by
# about one bf16 ulp (2^-8 relative). The loss is a mean over batch x sequence
# tokens of per-token differences of either sign, which leaves about 1e-4 at
# these sizes. A kernel that masks wrongly or drops a head moves every token's
# logits and shows as 1e-2 or more even at random initialisation.
FLASH_VS_BLOCKWISE_LOSS_ATOL = 5e-3

# The first train step computes its loss on the same batch and the same
# parameters as the forward-only evaluation before it; only the fusion of the
# backward pass around it differs.
TRAIN_VS_EVAL_LOSS_ATOL = 5e-3

# One-device, FSDP and FSDP x TP runs start from the same seed and see the same
# batches. They differ in where bf16 partial sums are added (a contraction split
# over tp, a gradient reduced over dp_shard), which Adam's normalised update
# amplifies a little every step. A sharding fault (a gradient reduction dropped
# or counted twice, a shard read as the whole) changes the step size itself
# and moves the loss by tenths within a few steps.
SHARDED_TRAJECTORY_ATOL = 5e-2

# Kernel and reference both accumulate in f32 and round the softmax weights to
# the value dtype before the last matmul, over different block orders, and
# round the output to bf16: a few bf16 ulps (2^-8 each) of the output's size.
# The error is taken relative to max(1, |reference|).
KERNEL_TOLERANCE = 2e-2

# Collectives are counted in the compiled step's text: instructions by their
# op name, and the fused forms the TPU compiler makes of a reduce-scatter (the
# all-reduce-scatter fusion, or a ring of collective-permutes fused with the
# matmul that feeds it) by the computations that fusions call.
_COLLECTIVE_OPS = (
    "all-gather", "reduce-scatter", "all-reduce", "all-to-all", "collective-permute",
)
_REDUCE_SCATTER_FUSIONS = ("all-reduce-scatter", "async_collective_fusion")
_REDUCE_SCATTERS = ("reduce-scatter",) + _REDUCE_SCATTER_FUSIONS
_GRADIENT_REDUCTIONS = _REDUCE_SCATTERS + ("all-reduce",)


class SmokeFailure(AssertionError):
    """A phase ran and what came out is wrong."""


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def report(phase: str, **facts) -> dict:
    facts = {"phase": phase, **facts}
    print(json.dumps(facts), flush=True)
    return facts


# ------------------------------------------------------------------ compile meter
class CompileMeter:
    """Seconds JAX spent in the backend compiler (or fetching from the
    persistent cache in its place), and how often the cache hit and how often
    it was written. JAX's listeners cannot be taken off again, so there is one
    meter for the process: :func:`compile_meter`."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.seconds, self.cache_hits, self.cache_misses)

    def since(self, mark) -> dict:
        return {
            "compile_s": round(self.seconds - mark[0], 2),
            "cache_hits": self.cache_hits - mark[1],
            "cache_misses": self.cache_misses - mark[2],
        }


@functools.lru_cache(maxsize=None)
def compile_meter() -> CompileMeter:
    return CompileMeter()


def memory_facts(devices) -> list:
    """Bytes in use now and at the process's peak, per device, where the
    backend reports them (the CPU backend does not)."""
    facts = []
    for device in devices:
        stats = device.memory_stats() or {}
        facts.append({
            "id": device.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return facts


def count_collectives(text: str) -> dict:
    counts = {
        op: len(re.findall(r"(?<![\w%.-])" + re.escape(op) + r"(?:-start)?\(", text))
        for op in _COLLECTIVE_OPS
    }
    for fusion in _REDUCE_SCATTER_FUSIONS:
        counts[fusion] = len(re.findall(r"calls=%" + re.escape(fusion) + r"\b", text))
    return counts


# ------------------------------------------------------------------------- train
def _reset_accelerator_state():
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _accelerator(parallelism, devices):
    """A fresh ``Accelerator(mixed_precision="bf16")``. ``devices`` is for a job
    on fewer devices than the process holds (the one-device run that the
    four-chip runs are compared with): ``ParallelismConfig`` always spans
    ``jax.devices()``, so the mesh is built here and handed to the shared state
    the Accelerator reads it from."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.parallel.mesh import build_mesh
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.constants import MESH_AXIS_ORDER

    _reset_accelerator_state()
    if devices is not None:
        check(parallelism is None, "a job on a device subset is unsharded")
        parallelism = ParallelismConfig(_total_devices=len(devices))
        state = AcceleratorState(mixed_precision="bf16", parallelism_config=parallelism)
        state.mesh = build_mesh(
            (len(devices),) + (1,) * (len(MESH_AXIS_ORDER) - 1),
            MESH_AXIS_ORDER, devices=devices,
        )
    return Accelerator(mixed_precision="bf16", parallelism_config=parallelism)


def _loss_with_attention(accelerator, model, batch, attention_impl: str) -> float:
    """Forward-only loss of ``model``'s parameters under another attention
    implementation: a second Model over the same arrays."""
    from accelerate_tpu.model import Model
    from accelerate_tpu.models.llama import llama_apply, llama_loss

    config = dataclasses.replace(model.config, attention_impl=attention_impl)
    twin = Model(
        functools.partial(llama_apply, config), model.params,
        mixed_precision_policy=model.policy,
    )
    return float(accelerator.eval_step(llama_loss, model=twin)(batch))


def _spread(tree, n_devices: int) -> dict:
    """How a pytree of arrays lies on the mesh: bytes held by each device and
    the fewest devices any large leaf (a hundredth of the tree or more: the
    matrices, not the norm scales that tensor parallelism replicates) is
    split over."""
    import jax

    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "addressable_shards")
    ]
    total = sum(leaf.nbytes for leaf in leaves)
    per_device: dict = {}
    fewest = n_devices
    for leaf in leaves:
        holders = set()
        for shard in leaf.addressable_shards:
            if shard.replica_id == 0:
                holders.add(shard.device.id)
                per_device[shard.device.id] = (
                    per_device.get(shard.device.id, 0) + shard.data.nbytes
                )
        if leaf.nbytes * 100 >= total:
            fewest = min(fewest, len(holders))
    return {
        "total_bytes": total,
        "bytes_per_device": [per_device[k] for k in sorted(per_device)],
        "fewest_devices_for_a_large_leaf": fewest,
    }


def train_phase(config, *, batch_size: int, seq_len: int, steps: int,
                parallelism=None, devices=None, reference_attention=None,
                seed: int = 0, name: str = "train") -> dict:
    """``create_llama`` → ``prepare`` → ``prepare_data_loader`` → ``train_step``
    for ``steps`` steps over two seeded batches seen again and again, so the
    loss has to fall. Raises :class:`SmokeFailure` on a loss that is not
    finite or does not fall, on a second compile of the step, on a
    ``reference_attention`` loss that disagrees with the configured one, and
    on a sharded job whose state is not spread over its mesh."""
    import jax
    import optax

    from accelerate_tpu.models.llama import create_llama, llama_loss
    from accelerate_tpu.utils import native

    meter = compile_meter()
    mark = meter.snapshot()
    t_start = time.perf_counter()
    accelerator = _accelerator(parallelism, devices)
    mesh_devices = list(accelerator.mesh.devices.flat)
    model = create_llama(config, seed=seed)
    n_params = model.num_parameters
    model, optimizer = accelerator.prepare(
        model, optax.adamw(LEARNING_RATE, weight_decay=0.01)
    )
    rng = np.random.default_rng(seed)
    data = {
        "input_ids": rng.integers(
            0, config.vocab_size, size=(2 * batch_size, seq_len), dtype=np.int32
        )
    }
    loader = accelerator.prepare_data_loader(data, batch_size=batch_size, drop_last=True)
    step = accelerator.train_step(llama_loss, max_grad_norm=1.0)
    first_batch = next(iter(loader))

    facts = {
        "model": "llama",
        "hidden": config.hidden_size, "ffn": config.intermediate_size,
        "heads": config.num_attention_heads, "kv_heads": config.num_key_value_heads,
        "head_dim": config.head_dim, "vocab": config.vocab_size,
        "window": config.sliding_window, "layers_kept": config.num_hidden_layers,
        "params": n_params, "attention_impl": config.attention_impl,
        "batch_size": batch_size, "seq_len": seq_len,
        "mesh": {k: v for k, v in accelerator.mesh.shape.items() if v > 1},
        "mesh_devices": [d.id for d in mesh_devices],
        "native_packing": "csrc" if native.get_packing_lib() is not None else "numpy",
    }

    if len(mesh_devices) > 1:
        facts["params_spread"] = _spread(model.params, len(mesh_devices))
        facts["opt_state_spread"] = _spread(optimizer.opt_state, len(mesh_devices))
    if reference_attention is not None:
        facts["eval_loss"] = {
            impl: _loss_with_attention(accelerator, model, first_batch, impl)
            for impl in (config.attention_impl, reference_attention)
        }

    losses, step_seconds = [], []
    while len(losses) < steps:
        for batch in loader:
            t0 = time.perf_counter()
            losses.append(float(step(batch)))  # float() waits for the device
            step_seconds.append(round(time.perf_counter() - t0, 3))
            if len(losses) == steps:
                break
    text = step.lower(first_batch).compile().as_text()
    facts.update(
        losses=losses, step_seconds=step_seconds,
        step_compiles=step.jitted._cache_size(),
        pallas_custom_calls=text.count("tpu_custom_call"),
        collectives=count_collectives(text),
        **meter.since(mark),
        wall_s=round(time.perf_counter() - t_start, 1),
        memory=memory_facts(mesh_devices),
    )
    del model, optimizer, step, loader, first_batch, text
    accelerator.free_memory()
    # the mesh must not outlive the job: model code reads it from the shared
    # state, and an engine built while it is live gets mesh-sharded outputs
    # for its meshless initial state and compiles its prefill a second time
    _reset_accelerator_state()
    jax.clear_caches()
    gc.collect()

    facts = report(name, **facts)  # printed whether or not they pass
    for which in ("params_spread", "opt_state_spread"):
        if which in facts:
            spread, n = facts[which], len(mesh_devices)
            check(spread["fewest_devices_for_a_large_leaf"] == n,
                  f"{name}: a large leaf of {which} lies on "
                  f"{spread['fewest_devices_for_a_large_leaf']} of {n} devices")
            check(len(spread["bytes_per_device"]) == n
                  and max(spread["bytes_per_device"]) <= 1.1 * spread["total_bytes"] / n,
                  f"{name}: {which} is not spread evenly: {spread}")
    check(all(np.isfinite(losses)), f"{name}: loss not finite: {losses}")
    # the two batches alternate: the last visit of each against its first
    check(np.mean(losses[-2:]) < np.mean(losses[:2]),
          f"{name}: loss does not fall: {losses}")
    if reference_attention is not None:
        here = facts["eval_loss"][config.attention_impl]
        ref = facts["eval_loss"][reference_attention]
        check(abs(here - ref) <= FLASH_VS_BLOCKWISE_LOSS_ATOL,
              f"{name}: loss {here} with {config.attention_impl} attention, "
              f"{ref} with {reference_attention}")
        check(abs(losses[0] - here) <= TRAIN_VS_EVAL_LOSS_ATOL,
              f"{name}: first train loss {losses[0]} against forward-only {here} "
              "on the same batch")
    check(facts["step_compiles"] == 1,
          f"{name}: the fused step compiled {facts['step_compiles']} times")
    return facts


# ----------------------------------------------------------------------- kernels
def _paged_case(rng, *, heads, kv_heads, head_dim, block_size, slots, blocks_per_row,
                window, quantized):
    """Seeded operands for the paged kernels: every slot owns a disjoint run of
    pool blocks and sits at its own position, from a fresh slot to a full row."""
    import jax.numpy as jnp

    num_blocks = slots * blocks_per_row + 1
    shape = (num_blocks, block_size, kv_heads, head_dim)
    tables = 1 + rng.permutation(num_blocks - 1).reshape(slots, blocks_per_row)
    max_pos = blocks_per_row * block_size - window
    pos = np.linspace(0, max_pos, slots).astype(np.int32)
    case = {
        "tables": jnp.asarray(tables, jnp.int32),
        "pos": jnp.asarray(pos),
        "q": jnp.asarray(rng.normal(size=(slots, window, heads, head_dim)), jnp.bfloat16),
    }
    if quantized:
        for which in ("k", "v"):
            case[which] = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
            case[which + "_scale"] = jnp.asarray(
                rng.uniform(2e-3, 2e-2, size=shape[:2]), jnp.float32
            )
    else:
        for which in ("k", "v"):
            case[which] = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    return case


def _window(rng, shape, quantized):
    """Fresh window K or V columns. For an int8 pool they are made exact in
    int8: the reference below reads its window back from the pool, quantized,
    while the kernel keeps it in bf16, and the comparison is of the kernels,
    not of that rounding. Integers up to 127 times 2^-6 are exact in bf16, and
    a 127 in every position makes kv_quantize choose the scale 2^-6."""
    import jax.numpy as jnp

    if not quantized:
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    ints = rng.integers(-127, 128, size=shape)
    ints[..., 0, 0] = 127
    return jnp.asarray(ints * 2.0 ** -6, jnp.bfloat16)


def _commit_window(pool, scale, tables, pos, window_kv, block_size):
    """The reference for verify reads the window from the pool: write it in,
    quantized like every committed column where the pool is int8."""
    import jax.numpy as jnp

    from accelerate_tpu.kvcache import kv_quantize

    w = window_kv.shape[1]
    abs_pos = pos[:, None] + jnp.arange(w)[None, :]
    blocks = jnp.take_along_axis(tables, abs_pos // block_size, axis=1)
    offsets = abs_pos % block_size
    if scale is None:
        return pool.at[blocks, offsets].set(window_kv.astype(pool.dtype)), None
    q, s = kv_quantize(window_kv)
    return pool.at[blocks, offsets].set(q), scale.at[blocks, offsets].set(s)


def kernel_parity_phase(head_shapes: dict, *, vocab_sizes, block_size: int = 16,
                        slots: int = 8, blocks_per_row: int = 32, window: int = 5,
                        seed: int = 0) -> dict:
    """``paged_flash_decode``, ``paged_flash_verify`` and ``fused_sample`` against
    ``ops.attention.paged_attention``, ``verify_attention`` and
    ``engine._sample_rows`` on the same seeded operands. ``head_shapes`` maps a
    name to ``(heads, kv_heads, head_dim)``. Outside any timing."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.engine import _sample_rows
    from accelerate_tpu.ops.attention import paged_attention, verify_attention
    from accelerate_tpu.ops.paged_decode import (
        fused_sample, paged_flash_decode, paged_flash_verify,
    )

    meter = compile_meter()
    mark = meter.snapshot()
    rng = np.random.default_rng(seed)
    errors = {}

    def max_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        check(got.shape == want.shape,
              f"kernel output shape {got.shape} != reference {want.shape}")
        check(np.isfinite(got).all(), "kernel output not finite")
        return float((np.abs(got - want) / np.maximum(1.0, np.abs(want))).max())

    for shape_name, (heads, kv_heads, head_dim) in head_shapes.items():
        for quantized in (False, True):
            pool = "int8" if quantized else "bfloat16"
            case = _paged_case(
                rng, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                block_size=block_size, slots=slots, blocks_per_row=blocks_per_row,
                window=window, quantized=quantized,
            )
            k_scale, v_scale = case.get("k_scale"), case.get("v_scale")
            tables, pos = case["tables"], case["pos"]

            def scaled(fn, ks, vs):
                return jax.jit(
                    functools.partial(fn, k_scale=ks, v_scale=vs) if quantized else fn
                )

            q1 = case["q"][:, :1]
            errors[f"decode/{shape_name}/{pool}"] = max_err(
                scaled(paged_flash_decode, k_scale, v_scale)(
                    q1, case["k"], case["v"], tables, pos),
                scaled(paged_attention, k_scale, v_scale)(
                    q1, case["k"], case["v"], tables, pos),
            )
            win_k, win_v = (
                _window(rng, (slots, window, kv_heads, head_dim), quantized)
                for _ in range(2)
            )
            k_ref, ks_ref = _commit_window(case["k"], k_scale, tables, pos, win_k, block_size)
            v_ref, vs_ref = _commit_window(case["v"], v_scale, tables, pos, win_v, block_size)
            errors[f"verify/{shape_name}/{pool}"] = max_err(
                scaled(paged_flash_verify, k_scale, v_scale)(
                    case["q"], case["k"], case["v"], win_k, win_v, tables, pos),
                scaled(verify_attention, ks_ref, vs_ref)(
                    case["q"], k_ref, v_ref, tables, pos),
            )

    sampled = {}
    temperature = jnp.asarray([0.0, 0.7, 1.3, 1.0, 0.5, 2.0, 0.9, 0.0], jnp.float32)
    top_p = jnp.asarray([1.0, 0.9, 1.0, 0.5, 0.95, 1.0, 0.8, 1.0], jnp.float32)
    for vocab in vocab_sizes:
        top_k = jnp.asarray([0, 50, 1, vocab, 5, 0, 200, 0], jnp.int32)
        logits = jnp.asarray(rng.normal(size=(8, vocab)) * 3, jnp.float32)
        subkeys = jax.random.split(jax.random.key(seed), 8)
        noise = jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), jnp.float32))(subkeys)
        sampled[str(vocab)] = {
            "kernel": np.asarray(jax.jit(fused_sample)(
                logits, noise, temperature, top_k, top_p)).tolist(),
            "reference": np.asarray(jax.jit(_sample_rows)(
                logits, subkeys, temperature, top_k, top_p)).tolist(),
        }

    facts = report(
        "kernels", max_error=errors, sampled_tokens=sampled,
        tolerance=KERNEL_TOLERANCE, **meter.since(mark),
    )
    for case_name, err in errors.items():
        check(err <= KERNEL_TOLERANCE, f"kernel {case_name}: max error {err}")
    for vocab, rows in sampled.items():
        check(rows["kernel"] == rows["reference"],
              f"fused_sample at vocab {vocab}: {rows}")
    return facts


# ------------------------------------------------------------------------- serve
@dataclasses.dataclass(frozen=True)
class Request:
    prompt_len: int
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = None
    top_p: float = None
    seed: int = 0


def _serve_requests(model, serving, requests, seed, timeout_s, rounds=1):
    """One server; in each round every request is submitted before any answer
    is read. Returns the engine, the results of each round and its seconds."""
    from accelerate_tpu.serving import InferenceServer

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, model.config.vocab_size, size=(r.prompt_len,), dtype=np.int32)
        for r in requests
    ]
    server = InferenceServer(model, serving)
    try:
        engine = server.engine
        results, seconds = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            futures = [
                server.submit(
                    prompt, max_new_tokens=r.max_new_tokens, temperature=r.temperature,
                    top_k=r.top_k, top_p=r.top_p, seed=r.seed,
                )
                for prompt, r in zip(prompts, requests)
            ]
            # result() raises the typed serving error that ended a request
            results.append([f.result(timeout=timeout_s) for f in futures])
            seconds.append(round(time.perf_counter() - t0, 2))
    finally:
        server.close(drain=True)
    return engine, results, seconds


def serve_phase(config, serving, requests, *, seed: int = 0,
                timeout_s: float = 900.0) -> dict:
    """``create_gpt2`` → ``InferenceServer`` in continuous mode with the paged
    cache → concurrent ``submit()`` of ``requests`` → every future resolves
    with the token count it asked for. The same requests then go through a
    server with the reference attention op; token agreement between the two is
    reported and not gated, since with random weights greedy tokens flip on
    rounding."""
    import jax

    from accelerate_tpu.models.gpt2 import create_gpt2

    meter = compile_meter()
    mark = meter.snapshot()
    t_start = time.perf_counter()
    model = create_gpt2(config, seed=seed)
    # round 1 compiles the engine's programs, round 2 finds them compiled
    engine, (results, again), round_seconds = _serve_requests(
        model, serving, requests, seed, timeout_s, rounds=2
    )

    decode_text = engine._decode_jit.lower(
        engine._donated, engine._carried, model.params, engine._backend.device_tables()
    ).compile().as_text()
    stats = engine.stats()
    facts = {
        "model": "gpt2", "hidden": config.hidden_size, "heads": config.num_attention_heads,
        "head_dim": config.head_dim, "vocab": config.vocab_size,
        "layers_kept": config.num_hidden_layers, "params": model.num_parameters,
        "mode": serving.mode, "kv_cache": serving.kv_cache,
        "attention_impl": engine.attention_impl,
        "slots": serving.engine_slots, "max_len": serving.engine_max_len,
        "block_size": serving.engine_block_size,
        "requests": [dataclasses.asdict(r) for r in requests],
        "tokens_returned": [len(r.tokens) for r in results],
        "degraded": [bool(r.degraded) for r in results],
        "same_tokens_both_rounds": all(
            (a.tokens == b.tokens).all() for a, b in zip(results, again)
        ),
        "programs": stats["programs"],
        "jit_signatures": {
            "decode": engine._decode_jit._cache_size(),
            "prefill": engine._prefill_jit._cache_size(),
        },
        "decode_pallas_custom_calls": decode_text.count("tpu_custom_call"),
        "kv_hbm_bytes": stats["kv"].get("hbm_bytes"),
        "round_seconds": round_seconds,
    }
    del engine, decode_text, stats

    if serving.attention_impl != "reference":
        reference = dataclasses.replace(serving, attention_impl="reference")
        _, (ref_results,), _ = _serve_requests(model, reference, requests, seed, timeout_s)
        same = [
            int((a.tokens[r.prompt_len:] == b.tokens[r.prompt_len:]).sum())
            for r, a, b in zip(requests, results, ref_results)
        ]
        facts["tokens_equal_to_reference_engine"] = {
            "equal": sum(same), "of": sum(r.max_new_tokens for r in requests),
            "per_request": same,
        }
    facts.update(meter.since(mark))
    facts["wall_s"] = round(time.perf_counter() - t_start, 1)
    facts["memory"] = memory_facts(jax.devices()[:1])
    del model
    jax.clear_caches()
    gc.collect()

    facts = report("serve", **facts)  # printed whether or not they pass
    check(facts["attention_impl"] == serving.attention_impl,
          f"serve: asked for attention_impl={serving.attention_impl!r}, "
          f"the engine runs {facts['attention_impl']!r}")
    for request, result in zip(requests, results):
        want = request.prompt_len + request.max_new_tokens
        check(len(result.tokens) == want and not result.degraded,
              f"serve: asked for {want} tokens, got {len(result.tokens)} "
              f"(degraded={result.degraded})")
        check(int(np.min(result.tokens)) >= 0
              and int(np.max(result.tokens)) < config.vocab_size,
              "serve: token ids outside the vocabulary")
    check(facts["same_tokens_both_rounds"],
          "serve: the same seeded requests gave other tokens the second time")
    # tests/test_kvcache.py: one prompt bucket gives one prefill and one decode
    check(facts["programs"] == {"prefill_insert": 1, "decode_step": 1},
          f"serve: engine dispatched programs {facts['programs']}")
    check(max(facts["jit_signatures"].values()) == 1,
          f"serve: engine programs compiled more than once: {facts['jit_signatures']}")
    return facts


# ------------------------------------------------------------------- four chips
def sharded_phase(config, deep_config, *, batch_size: int, seq_len: int, steps: int,
                  deep_steps: int) -> dict:
    """The job of :func:`train_phase` on a one-device mesh, under
    ``dp_shard_size=n`` and under ``dp_shard_size=n/2, tp_size=2``: the loss
    trajectories agree and the state is spread. Then ``deep_config``, which one
    device cannot hold, under ``dp_shard_size=n``."""
    import jax

    from accelerate_tpu.parallelism_config import ParallelismConfig

    n = len(jax.devices())
    check(n >= 4 and n % 2 == 0, f"the sharded comparison needs four devices, found {n}")
    common = dict(batch_size=batch_size, seq_len=seq_len, steps=steps)
    single = train_phase(config, devices=jax.devices()[:1], name="train_1dev", **common)
    fsdp = train_phase(
        config, parallelism=ParallelismConfig(dp_shard_size=n),
        name=f"train_fsdp{n}", **common,
    )
    fsdp_tp = train_phase(
        config, parallelism=ParallelismConfig(dp_shard_size=n // 2, tp_size=2),
        name=f"train_fsdp{n // 2}_tp2", **common,
    )
    deviations = {}
    for run in (fsdp, fsdp_tp):
        deviation = float(np.abs(np.subtract(run["losses"], single["losses"])).max())
        deviations[run["phase"]] = deviation
        check(deviation <= SHARDED_TRAJECTORY_ATOL,
              f"{run['phase']}: losses {run['losses']} against one device "
              f"{single['losses']}")
        # parameters gathered for use, gradients reduced into their shards:
        # XLA's CPU backend writes the latter as all-reduce and slice, the
        # TPU compiler as the fused forms main() asks for
        check(run["collectives"]["all-gather"] > 0
              and sum(run["collectives"][k] for k in _GRADIENT_REDUCTIONS) > 0,
              f"{run['phase']}: compiled step holds {run['collectives']}")

    # each device of the first sharded job, except device 0 which also ran the
    # one-device job, peaked at about a quarter of what one device needed
    single_peak = single["memory"][0]["peak_bytes_in_use"]
    if single_peak is not None:
        others = [m["peak_bytes_in_use"] for m in fsdp["memory"][1:]]
        check(max(others) <= 0.45 * single_peak,
              f"train_fsdp{n}: per-device peaks {others} against {single_peak} "
              "on one device")

    deep = train_phase(
        deep_config, parallelism=ParallelismConfig(dp_shard_size=n),
        batch_size=batch_size, seq_len=seq_len, steps=deep_steps,
        name=f"train_deep_fsdp{n}",
    )
    runs = (single, fsdp, fsdp_tp, deep)
    return report(
        "sharded", devices=n, max_loss_deviation_from_one_device=deviations,
        tolerance=SHARDED_TRAJECTORY_ATOL, deep_layers=deep["layers_kept"],
        deep_losses=deep["losses"],
        fewest_pallas_custom_calls=min(r["pallas_custom_calls"] for r in runs),
        fewest_reduce_scatters=min(
            sum(r["collectives"][k] for k in _REDUCE_SCATTERS) for r in runs[1:]
        ),
    )


# -------------------------------------------------------------------------- main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: train, kernels and serve on one chip. 4: only the sharded "
             "trainer on the four chips of one host, and what it is compared with.",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r} "
              f"({len(devices)} device(s)); nothing was run", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} "
              "device(s); nothing was run", file=sys.stderr)
        return 2

    from accelerate_tpu.models.gpt2 import GPT2Config
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.utils.dataclasses import ServingConfig
    from accelerate_tpu.utils.environment import enable_compile_cache

    cache_dir = enable_compile_cache()
    compile_meter()  # listening before the first compile
    report("device", platform=devices[0].platform, kind=devices[0].device_kind,
           count=len(devices), jax=jax.__version__, compile_cache_dir=cache_dir)

    # Mistral-7B-v0.1 at its published widths. Each layer holds 218M
    # parameters and the embedding with the head 262M; parameters and both
    # Adam moments are float32, 12 bytes a parameter between steps, and the
    # gradient is a temporary of the step: 16. Two layers: 698M parameters,
    # 8.4 GB held and 12.5 GB inside a step at 2048 tokens (the compiled
    # step's own count on the chip, PR 36), of a 16 GB chip.
    mistral = functools.partial(LlamaConfig.mistral_7b, attention_impl="flash")

    if args.chips == 1:
        train = train_phase(
            mistral(num_hidden_layers=2), batch_size=1, seq_len=2048, steps=6,
            reference_attention="blockwise",
        )
        check(train["pallas_custom_calls"] > 0,
              "train: no Pallas custom call in the compiled step: attention_impl="
              "'flash' was sent to another path")
        kernel_parity_phase(
            {"gpt2_large": (20, 20, 64), "mistral_7b": (32, 8, 128)},
            vocab_sizes=(50257, 32000),
        )
        serve = serve_phase(
            GPT2Config.gpt2_large(),
            ServingConfig(
                mode="continuous", kv_cache="paged", attention_impl="pallas",
                engine_slots=8, engine_max_len=512,
            ),
            [
                Request(prompt_len=17, max_new_tokens=24),
                Request(prompt_len=64, max_new_tokens=8),
                Request(prompt_len=130, max_new_tokens=32, temperature=0.8, top_k=50, seed=1),
                Request(prompt_len=200, max_new_tokens=16, temperature=1.0, top_p=0.9, seed=2),
                Request(prompt_len=33, max_new_tokens=40),
                Request(prompt_len=256, max_new_tokens=12, temperature=0.7, top_k=20,
                        top_p=0.95, seed=3),
            ],
        )
        check(serve["decode_pallas_custom_calls"] > 0,
              "serve: no Pallas custom call in the compiled decode program")
    else:
        # The same 2048 tokens a step as on one chip, as four rows (dp_shard=4
        # splits the batch) that the one-device run holds too. Eight layers:
        # 2.0B parameters, 32 GB of state, 8 GB a chip over four.
        sharded = sharded_phase(
            mistral(num_hidden_layers=2), mistral(num_hidden_layers=8),
            batch_size=4, seq_len=512, steps=6, deep_steps=4,
        )
        check(sharded["fewest_pallas_custom_calls"] > 0,
              "sharded: a compiled step holds no Pallas custom call")
        check(sharded["fewest_reduce_scatters"] > 0,
              "sharded: a compiled step reduces no gradient into its shard")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
