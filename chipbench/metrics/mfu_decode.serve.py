"""The decode program's share of the chip's peak while it runs: model FLOPs of the
tokens it decoded in the traced window (its executions times the mean number of
live slots, at the mean context the live slots held) over the summed device time
of its executions and the bf16 peak."""

from chipbench import lib, trace

METRIC = {"name": "mfu_decode.serve", "layer": "model step, decode", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

PROGRAM = r"^jit__decode_impl$"


def read(run):
    seconds = trace.programs_matching(run.summary, PROGRAM)
    counters = run.result["counters"]
    live = counters["live_slots_mean"]
    decode_flops = lib.find_count(run.ctx.config, "decode_flops")
    if not seconds or not live or decode_flops is None:
        return None
    context = counters["live_tokens_mean"] / live
    flops = len(seconds) * live * decode_flops(run.ctx.config, context)
    return 100.0 * flops / sum(seconds) / run.ctx.peaks["bf16_flops_per_s"]
