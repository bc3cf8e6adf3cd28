"""95th percentile of ``ServingResult.ttft_s``, the server's own stamp of the time
to a request's first token, over the requests that came back inside the window; a
failed request counts as the worst. The server does not stream, so no client can
clock this itself: it stands here, among the per-layer metrics, until one can."""

import math

from chipbench.lib import percentile

METRIC = {"name": "ttft_p95_ms.serve", "layer": "serving entry", "unit": "ms",
          "moves": "norm_latency_p50_ms", "source": "program_span"}


def read(run):
    value = percentile(run.result["spans"]["ttft_ms"], 95.0)
    return value if math.isfinite(value) else None
