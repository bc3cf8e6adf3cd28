"""The decode attention kernel's share of its roofline: the least time to read the
live keys and values (the mean of ``engine.live_tokens()`` polled during the traced
window, times bytes a token a layer, once for every call of the kernel, over the HBM
peak; bytes bind, the FLOPs are a few per byte) over the kernel's summed device time."""

from chipbench import lib, trace

METRIC = {"name": "paged_decode_roofline.serve", "layer": "serving kernels", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

# the name the program gives its ``pallas_call`` (``name=``) is the custom call's
# instruction name in the chip's trace, with the compiler's numbering behind it:
# ``%paged_decode.9 = ...``. A model's own kernel is none of this metric's.
KERNEL = r"^%?paged_decode[.\d]* = "


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    live = run.result["counters"]["live_tokens_mean"]
    call_bytes = lib.find_count(run.ctx.config, "paged_decode_bytes")
    if not seconds or not live or call_bytes is None:
        return None
    least = calls * call_bytes(run.ctx.config, live) / run.ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
