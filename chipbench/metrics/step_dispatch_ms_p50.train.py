"""What the host spends to send one step: the median duration of the
``train.step`` spans of the traced window (the call of the compiled step, which
returns before the device has run it)."""

from chipbench import hostspans
from chipbench.lib import median

METRIC = {"name": "step_dispatch_ms_p50.train", "layer": "training entry", "unit": "ms",
          "moves": "train_tokens_per_s_chip", "source": "program_span"}


def read(run):
    steps = hostspans.session_spans("train.step")
    return 1e3 * median(sp.t1 - sp.t0 for sp in steps) if steps else None
