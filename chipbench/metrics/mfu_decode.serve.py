"""The decode program's share of the chip's peak while it runs: model FLOPs of the
tokens it decoded in the traced window (its executions times the mean number of
live slots, at the mean context the live slots held) over the summed device time
of its executions and the bf16 peak."""

from chipbench import trace, work

METRIC = {"name": "mfu_decode.serve", "layer": "model step, decode", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

PROGRAM = r"^jit__decode_impl$"


def read(run):
    seconds = trace.programs_matching(run.summary, PROGRAM)
    counters = run.result["counters"]
    live = counters["live_slots_mean"]
    if not seconds or not live:
        return None
    context = counters["live_tokens_mean"] / live
    per_token = getattr(work, f"{run.ctx.config['family']}_decode_flops")(run.ctx.config, context)
    flops = len(seconds) * live * per_token
    return 100.0 * flops / sum(seconds) / run.ctx.peaks["bf16_flops_per_s"]
