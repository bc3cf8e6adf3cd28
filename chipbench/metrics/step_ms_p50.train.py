"""Median device time of one execution of the compiled train step."""

from chipbench import trace
from chipbench.lib import median

METRIC = {"name": "step_ms_p50.train", "layer": "training entry", "unit": "ms",
          "moves": "train_tokens_per_s_chip", "source": "device_trace"}

# Accelerator.train_step jits a function called `fused`
PROGRAM = r"^jit_fused$"


def read(run):
    seconds = trace.programs_matching(run.summary, PROGRAM)
    return 1e3 * median(seconds) if seconds else None
