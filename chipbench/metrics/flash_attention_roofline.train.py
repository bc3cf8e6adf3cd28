"""The flash attention kernels' share of their roofline: the least time the chip
could take for the attention of the traced steps (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak; at 2048 tokens the FLOPs bind) over the
summed device time of the forward kernel and the two backward kernels."""

from chipbench import trace, work

METRIC = {"name": "flash_attention_roofline.train", "layer": "training kernel", "unit": "%",
          "moves": "train_tokens_per_s_chip", "source": "device_trace"}

# no pallas_call of the program passes name=, so the trace names the kernels after
# whatever wraps them (closed_call, rematted_computation, checkpoint). What tells
# them apart is the custom-call target, and the train step holds no other Pallas
# kernel: the forward (run again in the backward pass under remat) and the two
# backward kernels, all of flash attention.
KERNELS = r'custom_call_target="tpu_custom_call"'
STEP_PROGRAM = r"^jit_fused$"


def read(run):
    seconds, _events = trace.time_matching(run.summary, KERNELS)
    steps = len(trace.programs_matching(run.summary, STEP_PROGRAM))
    if not seconds or not steps:
        return None
    tr = run.ctx.workload["traffic"]
    need = work.flash_train_work(run.ctx.config, tr["batch_size"], tr["seq_len"])
    least = max(need["flops"] / run.ctx.peaks["bf16_flops_per_s"],
                need["bytes"] / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * steps * least / seconds
