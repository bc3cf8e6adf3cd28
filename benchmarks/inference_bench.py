"""Big-model inference benchmark: load time + per-token decode latency.

The reference's headline table (BASELINE.md: GPT-J-6B 8.7s load / 0.05s per
token on 2 GPUs with hook-based dispatch). Our equivalents: sharded param
init/dispatch time, one-pass prefill time, and compiled-decode per-token
latency (measured over a fused multi-token scan).

Prints one JSON line.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import argparse
import json
import os
import resource
import time

import numpy as np


def _bench_config(target_gb: float):
    """The ONE sizing rule shared by the loader and the subprocess writer —
    they must agree or the loader's model diverges from the checkpoint."""
    from accelerate_tpu.models.llama import LlamaConfig

    hidden, inter, vocab = 4096, 11008, 32000
    per_layer_bytes = (4 * hidden * hidden + 3 * hidden * inter) * 4
    embed_bytes = 2 * vocab * hidden * 4  # embed + untied head
    layers = max(2, int((target_gb * 2**30 - embed_bytes) / per_layer_bytes))
    return LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=256,
    )


def big_load_rehearsal(target_gb: float, shard_gb: float = 1.0):
    """Multi-GB streamed-load rehearsal (VERDICT r3 next-round #7; reference
    big_model_inference README's load-time table): write a synthetic sharded
    safetensors checkpoint of ~target_gb, then stream it through
    load_checkpoint_and_dispatch into an ABSTRACT model and report wall time
    + peak host RSS. The assertion of interest: peak RSS stays ~ one model
    copy (device-resident arrays) + one tensor, NOT 2x — the whole-flat-dict
    load would double it."""
    import jax

    from accelerate_tpu.big_modeling import load_checkpoint_and_dispatch
    from accelerate_tpu.models.llama import create_llama
    from accelerate_tpu.parallelism_config import ParallelismConfig

    config = _bench_config(target_gb)

    ckpt_dir = os.environ.get("IBENCH_CKPT_DIR", "/tmp/bigload_ckpt")
    meta_path = os.path.join(ckpt_dir, "rehearsal_meta.json")
    if os.path.exists(ckpt_dir):
        # refuse a stale checkpoint from a different parameterization: the
        # sized model would not match it (KeyError) or the shard layout
        # would be misreported
        meta = {}
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
        if meta.get("target_gb") != target_gb or meta.get("shard_gb") != shard_gb:
            raise SystemExit(
                f"{ckpt_dir} holds a checkpoint for "
                f"{meta or 'unknown parameters'}, not "
                f"(target_gb={target_gb}, shard_gb={shard_gb}) — remove it "
                "or set IBENCH_CKPT_DIR"
            )
    if not os.path.exists(ckpt_dir):
        # write the synthetic checkpoint in a SUBPROCESS: ru_maxrss is a
        # high-water mark, so materializing the params in THIS process would
        # contaminate the loader's peak-RSS measurement
        import subprocess
        import sys

        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--write-ckpt",
             ckpt_dir, "--big-load-gb", str(target_gb),
             "--shard-gb", str(shard_gb)],
            check=True,
        )
        with open(meta_path, "w") as f:
            json.dump({"target_gb": target_gb, "shard_gb": shard_gb}, f)

    n_dev = len(jax.devices())
    pcfg = (
        ParallelismConfig(dp_shard_size=n_dev) if n_dev > 1 else ParallelismConfig()
    )
    mesh = pcfg.build_device_mesh()

    rss_before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss  # KiB
    model = create_llama(config, abstract=True)  # nothing materialized
    t0 = time.perf_counter()
    model = load_checkpoint_and_dispatch(model, ckpt_dir, mesh=mesh)
    jax.block_until_ready(model.params)
    load_s = time.perf_counter() - t0
    rss_after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    param_bytes = sum(
        int(np.prod(p.shape)) * p.dtype.itemsize
        for p in jax.tree_util.tree_leaves(model.params)
    )
    ckpt_bytes = sum(
        os.path.getsize(os.path.join(ckpt_dir, f))
        for f in os.listdir(ckpt_dir)
        if f.endswith(".safetensors")
    )
    result = {
        "metric": "big_model_streamed_load",
        "value": round(load_s, 2),
        "unit": "s",
        # reference GPT-J-6B fp16 (24 GB): 8.7 s load — scale by bytes
        "vs_baseline": round((8.7 * ckpt_bytes / 24e9) / load_s, 3) if load_s else None,
        "detail": {
            "checkpoint_gb": round(ckpt_bytes / 2**30, 2),
            "params_b": round(model.num_parameters / 1e9, 3),
            "n_shards": len([f for f in os.listdir(ckpt_dir) if f.endswith(".safetensors")]),
            "gb_per_s": round(ckpt_bytes / 2**30 / load_s, 2) if load_s else None,
            "peak_rss_gb": round(rss_after / 2**20, 2),
            "rss_before_gb": round(rss_before / 2**20, 2),
            # < ~1.3x the params proves streaming (an eager flat-dict load
            # peaks at ~2x: full host dict + device copies)
            "peak_rss_over_params": round(rss_after * 1024 / param_bytes, 2),
            "n_devices": n_dev,
        },
    }
    print(json.dumps(result))
    return result


def main():
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.inference import generate
    from accelerate_tpu.models.llama import LlamaConfig, create_llama
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.big_modeling import dispatch_model

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # size ladder: 1.3B-class first, backing off if the (possibly
        # contended — window-1 saw other tenants holding most of the
        # 16 GB) chip can't fit it. A measured small-model row beats a
        # RESOURCE_EXHAUSTED and says so in the JSON.
        candidates = [
            dict(hidden_size=int(os.environ.get("IBENCH_HIDDEN", 2048)),
                 intermediate_size=int(os.environ.get("IBENCH_INTER", 5504)),
                 num_hidden_layers=int(os.environ.get("IBENCH_LAYERS", 24))),
            dict(hidden_size=1024, intermediate_size=2816, num_hidden_layers=16),
            dict(hidden_size=512, intermediate_size=1408, num_hidden_layers=8),
        ]
        configs = [
            LlamaConfig(
                vocab_size=32000, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=2048,
                param_dtype=jnp.bfloat16, **c,
            )
            for c in candidates
        ]
        prompt_len, new_tokens = 128, 64
    else:
        configs = [LlamaConfig.tiny(param_dtype=jnp.bfloat16)]
        prompt_len, new_tokens = 16, 8

    n_dev = len(jax.devices())
    pcfg = ParallelismConfig(tp_size=n_dev) if n_dev > 1 else ParallelismConfig()
    mesh = pcfg.build_device_mesh()
    from accelerate_tpu.parallel.tp import tensor_parallel_rules

    backoff_note = None
    for i, config in enumerate(configs):
        try:
            t0 = time.perf_counter()
            model = create_llama(config, seed=0)
            model = dispatch_model(
                model, mesh=mesh,
                rules=tensor_parallel_rules() if n_dev > 1 else None,
            )
            _leaf = jax.tree_util.tree_leaves(model.params)[0]
            np.asarray(_leaf[(0,) * _leaf.ndim])  # 1-elem fetch forces the stream
            load_s = time.perf_counter() - t0

            rng = np.random.default_rng(0)
            ids = rng.integers(
                0, config.vocab_size, size=(1, prompt_len)
            ).astype(np.int32)

            # compile + warm
            out = generate(model, ids, max_new_tokens=new_tokens)
            _ = np.asarray(out)

            t0 = time.perf_counter()
            out = generate(model, ids, max_new_tokens=new_tokens)
            _ = np.asarray(out)  # the tokens reach the host inside the timing
            total_s = time.perf_counter() - t0
            per_token_s = total_s / new_tokens
            break
        except Exception as exc:  # noqa: BLE001 — back off and retry smaller
            if i + 1 >= len(configs):
                raise
            backoff_note = (
                f"h={config.hidden_size} failed "
                f"({type(exc).__name__}: {str(exc)[:120]}); backing off"
            )
            print(json.dumps({"note": backoff_note}), flush=True)
            jax.clear_caches()

    result = {
        "metric": "llama_decode_latency_per_token",
        "value": round(per_token_s, 5),
        "unit": "s/token",
        "vs_baseline": round(0.05 / per_token_s, 3) if per_token_s > 0 else None,
        "detail": {
            "params_m": round(model.num_parameters / 1e6, 1),
            "load_s": round(load_s, 2),
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "n_devices": n_dev,
            "generate_total_s": round(total_s, 3),
            **({"backoff": backoff_note} if backoff_note else {}),
        },
    }
    print(json.dumps(result))


def _write_ckpt(ckpt_dir: str, target_gb: float, shard_gb: float):
    """Subprocess helper: materialize + write the synthetic checkpoint."""
    import jax

    from accelerate_tpu.models.llama import init_llama_params
    from accelerate_tpu.utils.serialization import save_sharded_safetensors

    config = _bench_config(target_gb)
    os.makedirs(ckpt_dir, exist_ok=True)
    params = init_llama_params(config, jax.random.key(0))
    save_sharded_safetensors(
        jax.tree_util.tree_map(np.asarray, params), ckpt_dir,
        max_shard_size=f"{shard_gb}GB",
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--big-load-gb", type=float, default=None,
                        help="run the multi-GB streamed-load rehearsal "
                        "instead of the decode benchmark")
    parser.add_argument("--shard-gb", type=float, default=1.0)
    parser.add_argument("--write-ckpt", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.write_ckpt:
        _write_ckpt(args.write_ckpt, args.big_load_gb, args.shard_gb)
    elif args.big_load_gb:
        big_load_rehearsal(args.big_load_gb, args.shard_gb)
    else:
        main()
