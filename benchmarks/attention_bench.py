"""Attention kernel microbenchmark: flash (Pallas) vs blockwise vs xla.

Times forward and forward+backward across sequence lengths, plus the
sliding-window and GQA variants the flash kernel optimizes (window tiles
grid-pruned; kv never repeated). On CPU the Pallas kernel runs in interpret
mode — numbers are only meaningful on TPU; the harness is validated here.

Usage:
  python benchmarks/attention_bench.py [--seqs 2048 4096 8192] [--fwd_only]
Writes one JSON line per (impl, seq, variant) to stdout and
benchmarks/attention_results.jsonl.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # runnable as `python benchmarks/x.py`

import argparse
import json
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", type=int, nargs="+", default=[1024, 2048])
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--kv_heads", type=int, default=None)
    parser.add_argument("--head_dim", type=int, default=64)
    parser.add_argument("--window", type=int, default=None)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--fwd_only", action="store_true")
    parser.add_argument("--impls", nargs="+",
                        default=["flash", "blockwise", "xla"])
    parser.add_argument("--ring", type=int, default=0,
                        help="additionally bench ring attention (CP) over an "
                        "N-way cp mesh: ring+blockwise and ring+flash rows. "
                        "Needs >= N devices (virtual CPU mesh or a pod).")
    parser.add_argument("--out", default="benchmarks/attention_results.jsonl")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.attention import dispatch_attention

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    kvh = args.kv_heads or args.heads
    rows = []

    impls = list(args.impls)
    ring_fns = {}
    if args.ring > 1 and args.window is not None:
        # ring attention has no sliding-window mode; rows would run full
        # causal attention while the window-adjusted flops formula deflated
        # their TFLOP/s — not comparable, so skip instead of misreport
        print(json.dumps({"note": "--ring rows skipped: window unsupported"}))
    elif args.ring > 1:
        from accelerate_tpu.ops.ring_attention import make_ring_attention
        from accelerate_tpu.parallelism_config import ParallelismConfig

        n_dev = len(jax.devices())
        if n_dev % args.ring:
            raise SystemExit(f"--ring {args.ring} does not divide {n_dev} devices")
        pcfg = ParallelismConfig(cp_size=args.ring,
                                 dp_shard_size=n_dev // args.ring)
        mesh = pcfg.build_device_mesh()
        for name, impl in (("ring+blockwise", "blockwise"),
                           ("ring+flash", "flash")):
            ring_fns[name] = make_ring_attention(
                mesh, attention_impl=impl, kv_block=512
            )
        impls += list(ring_fns)

    for seq in args.seqs:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(args.batch, seq, args.heads, args.head_dim)), dtype)
        k = jnp.asarray(rng.normal(size=(args.batch, seq, kvh, args.head_dim)), dtype)
        v = jnp.asarray(rng.normal(size=(args.batch, seq, kvh, args.head_dim)), dtype)
        # visible (q, k) pair fraction: causal keeps ~half; a window W keeps
        # ~W*S - W^2/2 pairs of S^2 (a window >= seq is a no-op: 0.5)
        if args.window is None or args.window >= seq:
            pair_frac = 0.5
        else:
            w = args.window
            pair_frac = (w * seq - w * w / 2) / (seq * seq)
        flops_fwd = 4 * args.batch * args.heads * seq * seq * args.head_dim * pair_frac

        for impl in impls:
            if impl in ring_fns:
                fwd = jax.jit(lambda q, k, v, _f=ring_fns[impl]: _f(
                    q, k, v, causal=True))
            else:
                fwd = jax.jit(lambda q, k, v, _i=impl: dispatch_attention(
                    _i, q, k, v, causal=True, window=args.window))

            def loss(q, k, v, _f=fwd):
                return jnp.sum(_f(q, k, v).astype(jnp.float32))

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                jax.block_until_ready(fwd(q, k, v))  # compile
                if not args.fwd_only:
                    jax.block_until_ready(grad(q, k, v))
            except Exception as exc:  # noqa: BLE001 — record, don't die
                row = {"impl": impl, "seq": seq, "error": str(exc)[:200]}
                rows.append(row)
                print(json.dumps(row))
                continue

            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fwd(q, k, v)
            jax.block_until_ready(out)
            fwd_s = (time.perf_counter() - t0) / args.iters

            row = {
                "impl": impl, "seq": seq, "batch": args.batch,
                "heads": args.heads, "kv_heads": kvh, "window": args.window,
                "device": device.device_kind or device.platform,
                "fwd_ms": round(fwd_s * 1e3, 3),
                "fwd_tflops": round(flops_fwd / fwd_s / 1e12, 3),
            }
            if not args.fwd_only:
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    g = grad(q, k, v)
                jax.block_until_ready(g)
                bwd_s = (time.perf_counter() - t0) / args.iters
                row["fwdbwd_ms"] = round(bwd_s * 1e3, 3)
                # bwd ~2x fwd flops (dq + dkv) on top of the fwd recompute
                row["fwdbwd_tflops"] = round(3.5 * flops_fwd / bwd_s / 1e12, 3)
            rows.append(row)
            print(json.dumps(row))

    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
