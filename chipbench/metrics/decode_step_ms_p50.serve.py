"""Median device time of one execution of the engine's decode program."""

from chipbench import trace
from chipbench.lib import median

METRIC = {"name": "decode_step_ms_p50.serve", "layer": "model step, decode", "unit": "ms",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

PROGRAM = r"^jit__decode_impl$"


def read(run):
    seconds = trace.programs_matching(run.summary, PROGRAM)
    return 1e3 * median(seconds) if seconds else None
