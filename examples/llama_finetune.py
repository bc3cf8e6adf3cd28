"""Llama fine-tuning with FSDP sharding — the reference's
``benchmarks/fsdp2/main.py`` workload (Llama-2-7B full-shard fine-tune)
TPU-first: one fused train step, scan-over-layers, bf16, mesh from flags.

Synthetic token data by default (zero-egress safe); pass --checkpoint to load
safetensors weights via the sharded streaming loader.
"""

from __future__ import annotations

import argparse
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
from accelerate_tpu.parallelism_config import ParallelismConfig


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny", choices=["tiny", "7b", "bench"])
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--seq_len", type=int, default=512)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--checkpoint", default=None, help="safetensors dir to load")
    parser.add_argument("--dp_shard", type=int, default=-1)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--cp", type=int, default=1)
    args = parser.parse_args()

    presets = {
        "tiny": lambda: LlamaConfig.tiny(max_position_embeddings=args.seq_len),
        # remat_policy: "full" | "dots" | "dots_no_batch" | "minimal" |
        # "nothing", or "auto" (the default: train_step keeps the fastest that
        # fits the device); a policy stated here is never changed
        "7b": lambda: LlamaConfig.llama2_7b(
            max_position_embeddings=args.seq_len, remat_policy="dots"
        ),
        "bench": lambda: LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=args.seq_len,
        ),
    }
    config = presets[args.preset]()

    pcfg = ParallelismConfig(dp_shard_size=args.dp_shard, tp_size=args.tp, cp_size=args.cp)
    accelerator = Accelerator(parallelism_config=pcfg, mixed_precision="bf16")
    accelerator.print(f"{accelerator!r}")

    model = create_llama(config, seed=0)
    if args.checkpoint:
        from accelerate_tpu.big_modeling import load_checkpoint_in_model

        load_checkpoint_in_model(model, args.checkpoint, strict=False)
    optimizer = optax.adamw(args.lr, weight_decay=0.01)
    model, optimizer = accelerator.prepare(model, optimizer)
    model.policy = None  # model computes in bf16 internally
    step_fn = accelerator.train_step(llama_loss, max_grad_norm=1.0)

    rng = np.random.default_rng(0)
    data = {
        "input_ids": rng.integers(
            0, config.vocab_size, size=(args.batch_size * 4, args.seq_len)
        ).astype(np.int32)
    }
    loader = accelerator.prepare_data_loader(data, batch_size=args.batch_size, drop_last=True)

    tokens_per_step = args.batch_size * args.seq_len
    t0 = None
    done = 0
    while done < args.steps:
        for batch in loader:
            loss = step_fn(batch)
            done += 1
            if done == 2:
                jax.block_until_ready(loss)
                t0 = time.perf_counter()
                tokens = 0
            elif t0 is not None:
                tokens = (done - 2) * tokens_per_step
            if done >= args.steps:
                break
    loss = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tps = tokens / dt if dt > 0 else float("nan")
    accelerator.print(
        f"loss={loss:.4f} tokens/s={tps:,.0f} "
        f"(~{tps * llama_flops_per_token(config, args.seq_len) / 1e12:.1f} TFLOP/s)"
    )


if __name__ == "__main__":
    main()
