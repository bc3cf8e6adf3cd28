"""Share of the traced window the step loop spent waiting for its next batch: the
summed ``train.data_wait`` spans over the session's span of time, from the first
profiled span's start to the last one's end."""

from chipbench import hostspans

METRIC = {"name": "data_wait_share.train", "layer": "input pipeline", "unit": "%",
          "moves": "train_tokens_per_s_chip", "source": "program_span"}


def read(run):
    spans = hostspans.session_spans() or []
    waits = [sp.t1 - sp.t0 for sp in spans if sp.name == "train.data_wait"]
    if not waits:
        return None
    whole = max(sp.t1 for sp in spans) - min(sp.t0 for sp in spans)
    return 100.0 * sum(waits) / whole if whole > 0 else None
