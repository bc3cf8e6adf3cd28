"""95th percentile of ``(latency_s - ttft_s) / (new_tokens - 1)`` from the server's
own stamps: the time per output token after the first."""

import math

from chipbench.lib import percentile

METRIC = {"name": "tpot_p95_ms.serve", "layer": "engine step", "unit": "ms",
          "moves": "norm_latency_p50_ms", "source": "program_span"}


def read(run):
    value = percentile(run.result["spans"]["tpot_ms"], 95.0)
    return value if math.isfinite(value) else None
