"""How much of what the decode kernel walks is live: the mean, over the
``engine.decode_step`` spans of the traced window, of the live positions over the
positions the kernel computes on for one layer (each slot's live positions rounded
up to the kernel's chunk, and one chunk for a slot that holds nothing)."""

from chipbench import hostspans

METRIC = {"name": "paged_walk_fill.serve", "layer": "serving kernels", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.decode_step") or []
             if sp.attrs.get("kv_walked_tokens")]
    if not steps:
        return None
    return 100.0 * sum(a["kv_live_tokens"] / a["kv_walked_tokens"] for a in steps) / len(steps)
