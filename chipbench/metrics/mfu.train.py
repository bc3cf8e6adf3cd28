"""The whole training step's share of the chip's peak: model FLOPs of every token
the window finished (forward once, backward twice, causal attention, nothing that
is recomputed), over the window, the chips and the bf16 peak."""

METRIC = {"name": "mfu.train", "layer": "training entry", "unit": "%",
          "moves": "train_tokens_per_s_chip", "source": "host_clock"}


def read(run):
    flops = run.driver.flops(run.ctx, run.result)
    peak = run.ctx.peaks["bf16_flops_per_s"] * run.ctx.chips
    return 100.0 * flops / run.result["window_s"] / peak
