"""How unevenly a decode step's rows fall on the experts: the most rows any one
expert of any layer got (``moe_load_max``) over the mean rows an expert
(``moe_assignments / moe_expert_slots``), averaged over the decode steps read back
in the traced window (``engine.readback`` spans of kind ``decode``). 1 is an even
spread; the grouped matmul's longest group follows the maximum."""

from chipbench import hostspans

METRIC = {"name": "moe_load_max_over_mean.serve", "layer": "expert layer", "unit": "ratio",
          "moves": "norm_latency_p50_ms", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.readback") or []
             if sp.attrs.get("kind") == "decode" and sp.attrs.get("moe_assignments")]
    if not steps:
        return None
    return sum(a["moe_load_max"] * a["moe_expert_slots"] / a["moe_assignments"]
               for a in steps) / len(steps)
