"""Share of the traced window in which no operation ran on the device."""

METRIC = {"name": "device_idle.train", "layer": "device", "unit": "%",
          "moves": "train_tokens_per_s_chip", "source": "device_trace"}


def read(run):
    return 100.0 * run.summary.idle_share
