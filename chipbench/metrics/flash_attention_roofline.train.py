"""The flash attention kernels' share of their roofline: the least time the chip
could take for the attention of the traced steps (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak; at 2048 tokens the FLOPs bind) over the
summed device time of the forward kernel and the two backward kernels."""

from chipbench import lib, trace

METRIC = {"name": "flash_attention_roofline.train", "layer": "training kernel", "unit": "%",
          "moves": "train_tokens_per_s_chip", "source": "device_trace"}

# the names the program gives its ``pallas_call``s (``name=``) are the custom calls'
# instruction names in the chip's trace, with the compiler's numbering behind them:
# the forward (run again in the backward pass under remat) and the two backward
# kernels. A model's own kernel in the train step is none of this metric's.
KERNELS = r"^%?flash_(fwd|bwd_dq|bwd_dkv)[.\d]* = "
STEP_PROGRAM = r"^jit_fused$"


def read(run):
    seconds, _events = trace.time_matching(run.summary, KERNELS)
    steps = len(trace.programs_matching(run.summary, STEP_PROGRAM))
    step_work = lib.find_count(run.ctx.config, "flash_train_work")
    if not seconds or not steps or step_work is None:
        return None
    tr = run.ctx.workload["traffic"]
    need = step_work(run.ctx.config, tr["batch_size"], tr["seq_len"])
    least = max(need["flops"] / run.ctx.peaks["bf16_flops_per_s"],
                need["bytes"] / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * steps * least / seconds
