"""The decode attention kernel's share of its roofline: the least time to read the
live keys and values (the mean of ``engine.live_tokens()`` polled during the traced
window, times bytes a token a layer, once for every call of the kernel, over the HBM
peak; bytes bind, the FLOPs are a few per byte) over the kernel's summed device time."""

from chipbench import trace, work

METRIC = {"name": "paged_decode_roofline.serve", "layer": "serving kernels", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

# no pallas_call of the program passes name=, so the trace names the kernel after
# what wraps it (closed_call). What tells it from the sampler, the decode program's
# other Pallas kernel, is its shape: block tables in (s32) and one row a head out
# (bf16[slots,heads,1,head_dim]).
KERNEL = r'= bf16\[\d+,\d+,1,\d+\]\S* custom-call\(s32\[.*custom_call_target="tpu_custom_call"'


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    live = run.result["counters"]["live_tokens_mean"]
    if not seconds or not live:
        return None
    least = calls * work.paged_decode_bytes(run.ctx.config, live) / run.ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
