"""Share of the device's busy time that goes to moving the key/value pool about:
the summed self time of the operations, the Pallas kernels apart, whose result has
the pool's element type and the shape of the whole pool or of one layer's slice of
it, over the busy time of the traced window. The shapes are built from the cell's
files: ``[L, blocks, block_size, kv_heads, head_dim]``, ``L`` being the layers that
keep keys and values in the paged pool (``kv_layers`` of the configuration's counts
file), with the layer axis whole, 1 or left out, the two head axes apart or merged
into one, and the two leading axes merged (the form the kernels are handed). A
copy, a transpose, a slice or a write-back of that size scales with the pool; a
scatter that writes a few rows in place has the pool's shape too and takes
microseconds, so a program that leaves the pool where it lies reads near zero."""

from chipbench import lib, trace

METRIC = {"name": "kv_pool_copy_share.serve", "layer": "KV cache", "unit": "%",
          "moves": "serve_tokens_per_s", "source": "device_trace"}

ELEMENT = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}


def pattern(config: dict, serving: dict) -> str:
    """A regular expression for the names of the operations whose result is the
    pool or a layer's slice of it."""
    layers = lib.count(config, "kv_layers")(config)
    heads = config.get("num_key_value_heads") or config["num_attention_heads"]
    head_dim = config["head_dim"]
    block = serving.get("engine_block_size", 16)
    blocks = serving.get("engine_pool_blocks") or (
        serving["engine_slots"] * serving["engine_max_len"] // block + 1
    )
    element = ("s8" if serving["kv_cache"] == "paged_int8"
               else ELEMENT[config["precision"]["kv_cache"]])
    lead = rf"(?:(?:(?:1|{layers}),)?{blocks}|{layers * blocks})"
    tail = rf"{block},(?:{heads},{head_dim}|{heads * head_dim})"
    return rf"^%?[\w.\-]+ = {element}\[{lead},{tail}\](?!.*tpu_custom_call)"


def read(run):
    serving = run.ctx.workload.get("serving", {})
    if not serving.get("kv_cache", "").startswith("paged") or not run.summary.busy_s:
        return None
    if lib.find_count(run.ctx.config, "kv_layers") is None:  # pattern() would end the run
        return None
    seconds, _ = trace.time_matching(run.summary, pattern(run.ctx.config, serving))
    return 100.0 * seconds / run.summary.busy_s
