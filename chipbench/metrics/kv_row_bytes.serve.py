"""Bytes one position holds in the cache over all layers, as the engine's backend
allocated its leaves: ``kv_row_bytes`` on the ``engine.decode_step`` spans of the
traced window (the same on every one of them). For a latent cache one row a layer,
no second leaf of values: what decides how many slots of how many positions a chip
holds beside its weights. A program whose spans lack the counter gives nothing."""

from chipbench import hostspans

METRIC = {"name": "kv_row_bytes.serve", "layer": "KV cache", "unit": "bytes",
          "moves": "serve_tokens_per_s", "source": "program_counter"}


def read(run):
    steps = [sp.attrs["kv_row_bytes"] for sp in hostspans.session_spans("engine.decode_step") or []
             if sp.attrs.get("kv_row_bytes")]
    if not steps:
        return None
    return sum(steps) / len(steps)
