"""Operations and bytes that the mathematics needs, from shapes alone: the rules
of the count and the formulas that are no one family's.

Model FLOPs count a multiply-add as two, causal attention at the keys a query
really sees, the forward pass once and the backward pass twice; nothing that is
recomputed and no padding. A family's own counts are a file
``chipbench/counts/<name>.py``, found by the configuration's ``counts`` key or
else its ``family`` (``lib.count``); this file names no family. ``cfg`` is a
configuration's file as a dict.
"""

from __future__ import annotations


def mean_keys(seq_len: int, window=None) -> float:
    """Keys a query sees, averaged over the positions of a causal sequence."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    # positions below the window see position+1 keys, the others `window`
    return (window * (window + 1) / 2.0 + (seq_len - window) * window) / seq_len


def kv_bytes_per_token_per_layer(cfg: dict, itemsize: int = 2) -> int:
    """Keys and values one token adds to one attention layer's cache."""
    heads = cfg.get("num_key_value_heads", cfg["num_attention_heads"])
    return 2 * heads * cfg["head_dim"] * itemsize
