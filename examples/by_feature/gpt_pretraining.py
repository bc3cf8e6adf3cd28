"""GPT-2 pretraining across mesh axes — the TPU-native analogue of the
reference's Megatron-LM GPT pretraining example
(/root/reference/examples/by_feature/megatron_lm_gpt_pretraining.py).

Where the reference delegates TP/PP/DP to the megatron-lm engine (a 1,248-line
adapter), here the same layout is three ParallelismConfig integers on one
mesh: Megatron-style tensor parallelism is a sharding rule set, data
parallelism a batch axis, sequence/context parallelism a ring schedule. The
training loop is the plain fused-step loop — no engine-specific branches.

Run (8-way virtual mesh on CPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/by_feature/gpt_pretraining.py --tp 2 --dp_shard 4 --steps 8
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.models.gpt2 import GPT2Config, create_gpt2, gpt2_loss
from accelerate_tpu.parallelism_config import ParallelismConfig


def synthetic_documents(vocab_size: int, steps: int, batch: int, seq_len: int, seed=0):
    """Zero-egress stand-in for the reference's wikitext stream: documents of
    random lengths packed into fixed-length rows (what its group_texts does)."""
    rng = np.random.default_rng(seed)
    stream = rng.integers(4, vocab_size, size=steps * batch * seq_len + 1)
    # sprinkle EOS-ish boundaries so the model sees document structure
    stream[rng.random(stream.shape) < 0.01] = 3
    tokens = stream[: steps * batch * seq_len].reshape(steps, batch, seq_len)
    return tokens.astype(np.int32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny", choices=["tiny", "small", "medium"])
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--lr", type=float, default=6e-4)
    parser.add_argument("--warmup", type=int, default=4)
    parser.add_argument("--dp_shard", type=int, default=-1)
    parser.add_argument("--tp", type=int, default=1)
    args = parser.parse_args()

    presets = {
        "tiny": lambda: GPT2Config.tiny(max_position_embeddings=args.seq_len),
        "small": lambda: GPT2Config.gpt2_small(
            max_position_embeddings=args.seq_len, use_chunked_ce=True
        ),
        # remat_policy: "full" | "dots" | "dots_no_batch" | "minimal" |
        # "nothing", or "auto" (the default: train_step keeps the fastest that
        # fits the device); a policy stated here is never changed
        "medium": lambda: GPT2Config.gpt2_medium(
            max_position_embeddings=args.seq_len, use_chunked_ce=True,
            remat_policy="minimal",
        ),
    }
    config = presets[args.preset]()

    pcfg = ParallelismConfig(dp_shard_size=args.dp_shard, tp_size=args.tp)
    accelerator = Accelerator(parallelism_config=pcfg, mixed_precision="bf16")
    accelerator.print(f"{accelerator!r}")

    model = create_gpt2(config, seed=0)
    model = accelerator.prepare(model)
    model.policy = None  # the model handles bf16 compute internally

    # the reference's get_scheduler("linear", warmup) equivalent, natively
    schedule = optax.join_schedules(
        [
            optax.linear_schedule(0.0, args.lr, args.warmup),
            optax.linear_schedule(args.lr, 0.0, max(args.steps - args.warmup, 1)),
        ],
        [args.warmup],
    )
    optimizer = accelerator.prepare(optax.adamw(schedule, weight_decay=0.01))

    step_fn = accelerator.train_step(gpt2_loss, max_grad_norm=1.0, multi_step=True)
    tokens = synthetic_documents(
        config.vocab_size, args.steps, args.batch_size, args.seq_len
    )

    # warm the fused program at the real shape first (the multi-step scan
    # compiles per leading-dim), so the reported tok/s excludes compile
    losses = np.asarray(step_fn({"input_ids": tokens}))
    t0 = time.time()
    losses = np.asarray(step_fn({"input_ids": tokens}))
    dt = time.time() - t0
    tok_s = args.steps * args.batch_size * args.seq_len / dt
    accelerator.print(
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps "
        f"({tok_s:,.0f} tok/s)"
    )
    assert np.isfinite(losses).all(), "training diverged"


if __name__ == "__main__":
    main()
