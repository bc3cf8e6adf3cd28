"""The zero-compute experts' share of the chosen (row, expert) pairs: over the
decode steps read back in the traced window (``engine.readback`` spans of kind
``decode``), ``moe_zero_pairs`` over ``moe_pairs`` (every expert layer's pairs). A
pair that chose an identity expert reaches no grouped matmul: the share is the
part of a token's top-k that costs no weights' bytes, which LongCat-Flash's
selection bias holds near a third in training. Where no span carries the counters
(a family without zero-compute experts, or the parent's program) it gives nothing."""

from chipbench import hostspans

METRIC = {"name": "moe_zero_share.serve", "layer": "expert layer", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.readback") or []
             if sp.attrs.get("kind") == "decode" and sp.attrs.get("moe_pairs")]
    if not steps:
        return None
    return 100.0 * sum(a["moe_zero_pairs"] for a in steps) / sum(a["moe_pairs"] for a in steps)
