"""What the host does in one pass of the server's scheduler: the median, over the
``serving.tick`` spans of the traced window, of the span's duration less the
``engine.readback`` spans inside it (the one place the serving thread waits for
the device). Once the device's step is shorter than this, this bounds the tokens a
second."""

from chipbench import hostspans
from chipbench.lib import median

METRIC = {"name": "tick_host_ms_p50.serve", "layer": "engine scheduler", "unit": "ms",
          "moves": "serve_tokens_per_s", "source": "program_span"}


def host_seconds(spans) -> list:
    """A tick's own seconds, for every tick among ``spans``."""
    by_id = {sp.id: sp for sp in spans}
    waited = {sp.id: 0.0 for sp in spans if sp.name == "serving.tick"}
    for sp in spans:
        if sp.name != "engine.readback":
            continue
        above = by_id.get(sp.parent)
        while above is not None and above.id not in waited:
            above = by_id.get(above.parent)
        if above is not None:
            waited[above.id] += sp.t1 - sp.t0
    return [by_id[i].t1 - by_id[i].t0 - w for i, w in waited.items()]


def read(run):
    seconds = host_seconds(hostspans.session_spans() or [])
    return 1e3 * median(seconds) if seconds else None
