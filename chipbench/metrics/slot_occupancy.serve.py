"""How full the scheduler kept the decode program: the mean of
``engine.live_count()``, polled through the window, over the slots."""

METRIC = {"name": "slot_occupancy.serve", "layer": "engine scheduler", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "program_counter"}


def read(run):
    counters = run.result["counters"]
    if not counters["live_slots_mean"]:
        return None
    return 100.0 * counters["live_slots_mean"] / counters["slots"]
