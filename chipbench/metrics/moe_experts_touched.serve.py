"""Experts that got at least one row, of all there are: over the decode steps read
back in the traced window (``engine.readback`` spans of kind ``decode``),
``moe_experts_touched`` (summed over the expert layers) over ``moe_expert_slots``
(layers x experts). What a decode step has to read of the experts' weights."""

from chipbench import hostspans

METRIC = {"name": "moe_experts_touched.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "program_counter"}


def decode_steps(spans) -> list:
    return [sp.attrs for sp in spans or []
            if sp.attrs.get("kind") == "decode" and sp.attrs.get("moe_expert_slots")]


def read(run):
    steps = decode_steps(hostspans.session_spans("engine.readback"))
    if not steps:
        return None
    return 100.0 * (sum(a["moe_experts_touched"] for a in steps)
                    / sum(a["moe_expert_slots"] for a in steps))
