"""95th percentile of ``ServingResult.queue_wait_s`` over the requests that came
back inside the window."""

from chipbench.lib import percentile

METRIC = {"name": "queue_wait_p95_ms.serve", "layer": "serving entry", "unit": "ms",
          "moves": "norm_latency_p50_ms", "source": "program_span"}


def read(run):
    values = run.result["spans"]["queue_wait_ms"]
    return percentile(values, 95.0) if values else None
