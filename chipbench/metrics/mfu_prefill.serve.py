"""The prefill program's share of the chip's peak while it runs: model FLOPs of the
real prompt tokens sent in the traced window (padding to the bucket is not work)
over the summed device time of the prefill program's executions and the bf16 peak."""

from chipbench import trace

METRIC = {"name": "mfu_prefill.serve", "layer": "model step, prefill", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "device_trace"}

PROGRAM = r"^jit__prefill_impl$"


def read(run):
    seconds = trace.programs_matching(run.summary, PROGRAM)
    counters = run.result["counters"]
    if not seconds or not counters["requests_sent"]:
        return None
    # the trace may hold an execution more or less than the clients sent requests
    flops = counters["prefill_flops_sent"] * len(seconds) / counters["requests_sent"]
    return 100.0 * flops / sum(seconds) / run.ctx.peaks["bf16_flops_per_s"]
