"""Share of the device's busy time that the expert layers' grouped matmuls take:
the summed self time of the operations named ``ragged-dot-none`` (what the chip's
compiler makes of the program's ``jax.lax.ragged_dot``; see
``moe_experts_roofline.serve``) over the busy time of the traced window."""

from chipbench import trace

METRIC = {"name": "moe_experts_share.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?ragged-dot(?!-metadata)[\w.\-]* = "


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    if not calls or not run.summary.busy_s:
        return None
    return 100.0 * seconds / run.summary.busy_s
