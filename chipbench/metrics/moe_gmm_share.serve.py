"""Share of the device's busy time that the expert layers' grouped matmuls take,
where the program computes them with its own Pallas kernel: the summed self time of
the operations named ``moe_gmm`` (``accelerate_tpu/ops/grouped_matmul.py``; see
``moe_gmm_roofline.serve``) over the busy time of the traced window. A program
without the kernel gives nothing here."""

from chipbench import trace

METRIC = {"name": "moe_gmm_share.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?moe_gmm[.\d]* = "


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    if not calls or not run.summary.busy_s:
        return None
    return 100.0 * seconds / run.summary.busy_s
