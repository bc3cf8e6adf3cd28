"""Share of the traced window in which no operation ran on the device."""

METRIC = {"name": "device_idle.serve", "layer": "device", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "device_trace"}


def read(run):
    return 100.0 * run.summary.idle_share
