"""What a causal-LM family hands the serving path: its step functions and a
description of the state a sequence keeps between steps.

The engine (``engine.py``), the static ``generate()`` (``inference.py``) and
the cache backends (``kvcache.py``) ask a model's config for this and never
for its class: ``config.serving_family()``. A new family is served by
returning one of these from its config; nothing in the serving path names it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

__all__ = ["ServingFamily"]


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    """Step functions, all taking ``(config, params, ...)``:

    * ``prefill(config, params, input_ids, max_len)`` -> ``(logits, cache)``:
      one forward over a batch of whole prompts, logits at the last position.
    * ``prefill_at(config, params, input_ids, max_len, last_index)``: the same
      over right-padded prompts, logits (and any recurrent state) taken at each
      row's ``last_index``.
    * ``decode_step(config, params, cache, token, pos, *, kv_layout=None)`` ->
      ``(logits, cache)``: one token a row.
    * ``verify_step(config, params, cache, tokens, pos, *, kv_layout=None)`` ->
      ``(logits, window_kv)``: a window of tokens a row, the cache read only.
      ``None`` where the family has none: what rests on it (n-gram
      speculation, chunked prefill) is then refused when an engine is built.

    The state. ``kv_layers`` layers keep keys and values, ``kv_heads`` heads of
    ``head_dim`` each: the cache's ``"k"`` and ``"v"`` leaves have that many
    layers on their first axis, whatever the model's depth. A family whose
    other layers keep a fixed-size recurrent state a sequence (a short
    convolution's last inputs) says so with ``recurrent_layers`` and the state's
    ``recurrent_shape`` a layer a sequence: the cache then has a third leaf
    ``"recurrent"`` of ``(recurrent_layers, rows, *recurrent_shape)``, which
    ``prefill_at`` returns as of each prompt's true last position and
    ``decode_step`` advances.

    A latent cache. A family whose values are a prefix of its keys' own rows
    (latent attention: one compressed row a token a layer, shared by every query
    head) sets ``value_dim``: the cache then has the one leaf ``"k"`` of
    ``kv_heads * head_dim`` values a position (``head_dim`` is the row's whole
    width) and no ``"v"``; a row's first ``value_dim`` columns are also its
    value, the step hands the seam a query as wide as a row and gets a result
    ``value_dim`` wide a head (kvcache.py, "A latent row").

    ``step_summary``: where set, ``prefill``, ``prefill_at`` and ``decode_step``
    return a third value, a dict of small device arrays that count what the
    step did (rows an expert got); it rides the engine's readback ring beside
    the tokens, and ``step_summary(host arrays)`` turns it into the scalars the
    engine puts on the span that reads the step back.
    """

    prefill: Callable
    prefill_at: Callable
    decode_step: Callable
    verify_step: Optional[Callable]
    kv_layers: int
    kv_heads: int
    head_dim: int
    recurrent_layers: int = 0
    recurrent_shape: Tuple[int, ...] = ()
    step_summary: Optional[Callable] = None
    value_dim: Optional[int] = None
