"""How much of what the cache reserves holds keys and values: the mean, over the
``engine.decode_step`` spans of the traced window, of the live positions over the
reserved ones (whole blocks, for a request's whole budget)."""

from chipbench import hostspans

METRIC = {"name": "kv_live_of_reserved.serve", "layer": "KV cache", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "program_counter"}


def read(run):
    steps = [sp.attrs for sp in hostspans.session_spans("engine.decode_step") or []
             if sp.attrs.get("kv_reserved_tokens")]
    if not steps:
        return None
    return 100.0 * sum(a["kv_live_tokens"] / a["kv_reserved_tokens"] for a in steps) / len(steps)
