"""How many copies the decode kernel issues a block it copies: 100 times the copy
descriptors over the live blocks, counters ``kv_copy_descriptors`` and
``kv_live_blocks`` of each ``engine.decode_step`` span of the traced window, the
mean over those spans. 100% is a copy a block; a chunk whose live blocks are one
run in the pool takes one copy for each set bit of their count."""

from chipbench import hostspans

METRIC = {"name": "paged_copies_per_block.serve", "layer": "serving kernels", "unit": "%",
          "moves": "norm_latency_p50_ms", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.decode_step") or []
             if sp.attrs.get("kv_live_blocks")]
    if not steps:
        return None
    return 100.0 * sum(a["kv_copy_descriptors"] / a["kv_live_blocks"] for a in steps) / len(steps)
