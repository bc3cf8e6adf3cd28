"""Device time of the sampling kernel a decode step: the summed self time of the
operations that the program names ``fused_sample`` (``name=`` on its
``pallas_call``) over their calls, one a step. A :class:`TraceSummary` keeps sums
and counts, so this is the mean over the calls; a kernel of fixed shapes hardly
varies, and the median the name promises is within that of it."""

from chipbench import trace

METRIC = {"name": "sampler_ms_p50.serve", "layer": "serving kernels", "unit": "ms",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?fused_sample[.\d]* = "


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    return 1e3 * seconds / calls if calls else None
