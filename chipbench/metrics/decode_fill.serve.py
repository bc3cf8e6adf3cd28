"""Rows of the decode program that did useful work: the mean, over the
``engine.decode_step`` spans of the traced window, of the slots that were decoding
over all slots, counted where the step is dispatched (a slot that holds a
finished request not yet retired is not decoding)."""

from chipbench import hostspans

METRIC = {"name": "decode_fill.serve", "layer": "engine scheduler", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.decode_step") or []
             if sp.attrs.get("slots")]
    if not steps:
        return None
    return 100.0 * sum(a["decoding"] / a["slots"] for a in steps) / len(steps)
