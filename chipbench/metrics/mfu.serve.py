"""The whole serving step's share of the chip's peak: model FLOPs of every real
prompt token prefilled and every token decoded inside the window (padding is not
work), over the window and the bf16 peak."""

METRIC = {"name": "mfu.serve", "layer": "engine step", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "host_clock"}


def read(run):
    peak = run.ctx.peaks["bf16_flops_per_s"] * run.ctx.chips
    return 100.0 * run.result["flops"] / run.result["window_s"] / peak
