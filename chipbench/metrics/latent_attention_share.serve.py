"""Share of the device's busy time that the walk over a latent cache takes: the
summed self time of the decode attention kernel (``paged_decode``, see
``paged_decode_roofline.serve``) over the busy time of the traced window, where the
configuration's cache is latent (its counts file has ``stored_row``: one row a token
a layer that is key and value at once). What the latent row is for is the memory it
leaves free; this says what reading it costs a decode step. A configuration that
caches keys and values a head gives nothing here."""

from chipbench import lib, trace

METRIC = {"name": "latent_attention_share.serve", "layer": "serving kernels", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "device_trace"}

KERNEL = r"^%?paged_decode[.\d]* = "


def read(run):
    seconds, calls = trace.time_matching(run.summary, KERNEL)
    if not calls or not run.summary.busy_s or lib.find_count(run.ctx.config, "stored_row") is None:
        return None
    return 100.0 * seconds / run.summary.busy_s
