"""Mixture-of-Experts routing and expert-parallel FFN.

The reference has NO first-class expert parallelism — only DeepSpeed MoE
leaf-module marking and Megatron MoE config parsing (SURVEY §2.4 EP row:
"Build EP natively ... a genuine extension beyond the reference").

Design: GShard/Switch-style *dense dispatch* — top-k routing materialized as
a (tokens, experts, capacity) one-hot dispatch tensor consumed by two
einsums. No ragged shapes, no host control flow: the dispatch einsums lower
to all-to-alls when the expert dim is sharded over the ``ep`` mesh axis, and
the MXU stays busy on the expert FFN matmuls. Capacity bounds make every
shape static (XLA requirement); overflow tokens are dropped (standard Switch
behavior) and counted in the aux metrics.

Beside it, :func:`dropless_moe` is the expert layer a server needs: sigmoid
scores, every token reaches the experts it chose whatever the rest of the
batch chose, rows sorted by expert into grouped matmuls (the Pallas kernel
``moe_gmm`` of :mod:`.grouped_matmul`), and it is told which experts it holds.
"""

from __future__ import annotations

import math

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .grouped_matmul import group_visits, grouped_matmul

__all__ = [
    "Routing", "route_topk", "moe_ffn", "load_balancing_loss", "router_z_loss",
    "route_sigmoid_topk", "dropless_moe",
]


class Routing(NamedTuple):
    dispatch: jax.Array  # (N, E, C) 0/1 — token n → expert e at slot c
    combine: jax.Array  # (N, E, C) float — gating weights for the way back
    aux_loss: jax.Array  # scalar load-balancing loss
    router_probs: jax.Array  # (N, E)


def route_topk(
    router_logits: jax.Array,
    num_selected: int,
    capacity: int,
    *,
    jitter_key: Optional[jax.Array] = None,
) -> Routing:
    """Top-k token→expert assignment with per-expert capacity.

    ``router_logits``: (N, E). Position within each expert's capacity buffer
    is assigned first-come-first-served by token order (cumsum trick).
    """
    n, e = router_logits.shape
    if jitter_key is not None:
        router_logits = router_logits + 1e-2 * jax.random.normal(jitter_key, router_logits.shape)
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # (N, E)

    dispatch = jnp.zeros((n, e), dtype=jnp.float32)
    gates = jnp.zeros((n, e), dtype=jnp.float32)
    remaining = probs
    for _ in range(num_selected):
        choice = jnp.argmax(remaining, axis=-1)  # (N,)
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)
        dispatch = dispatch + onehot
        gates = gates + onehot * probs
        remaining = remaining * (1.0 - onehot)

    # capacity: position of each token within its expert's queue
    position_in_expert = (jnp.cumsum(dispatch, axis=0) - dispatch) * dispatch  # (N, E)
    within_capacity = (position_in_expert < capacity).astype(jnp.float32) * dispatch
    gates = gates * within_capacity

    # renormalize the surviving gates per token (Mixtral convention)
    denom = jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates / jnp.maximum(denom, 1e-9)

    slot = jax.nn.one_hot(position_in_expert.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch_tensor = within_capacity[..., None] * slot  # (N, E, C)
    combine_tensor = gates[..., None] * slot  # (N, E, C)

    aux = load_balancing_loss(probs, dispatch)
    return Routing(dispatch_tensor, combine_tensor, aux, probs)


def router_z_loss(router_logits: jax.Array) -> jax.Array:
    """ST-MoE router z-loss: mean logsumexp(logits)² — keeps router logits
    small so the f32 softmax stays well-conditioned in long bf16 runs
    (Zoph et al. 2022, eq. 5). Scale with ``router_z_loss_coef`` (1e-3
    is the paper default) and add to the load-balancing aux."""
    z = jax.scipy.special.logsumexp(router_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(z))


def load_balancing_loss(router_probs: jax.Array, dispatch_mask: jax.Array) -> jax.Array:
    """Switch-Transformer aux loss: E * Σ_e fraction_tokens_e · mean_prob_e —
    minimized by a uniform assignment."""
    e = router_probs.shape[-1]
    fraction = jnp.mean(dispatch_mask, axis=0)  # (E,)
    mean_prob = jnp.mean(router_probs, axis=0)  # (E,)
    return e * jnp.sum(fraction * mean_prob)


def moe_ffn(
    x: jax.Array,
    router_kernel: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    num_selected: int = 2,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
    aux_loss_coef: float = 1.0,
    router_z_loss_coef: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """SwiGLU expert FFN with top-k routing.

    Shapes: x (B, S, D); router (D, E); experts w_gate/w_up (E, D, I),
    w_down (E, I, D). Shard E over the ``ep`` mesh axis (parallel/ep.py
    rules): the dispatch/combine einsums then lower to all-to-alls over ICI.
    Returns (output (B, S, D), aux_loss scalar).
    """
    b, s, d = x.shape
    e = router_kernel.shape[1]
    n = b * s
    tokens = x.reshape(n, d)
    # ceil (not floor) and a num_selected floor: small decode batches would
    # otherwise round capacity below what even perfectly-balanced routing
    # needs, silently dropping tokens to the residual path
    capacity = max(num_selected, math.ceil(capacity_factor * num_selected * n / e))

    router_logits = tokens.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    routing = route_topk(router_logits, num_selected, capacity)
    # the returned aux is PRE-SCALED: coef * load-balance + coef_z * z-loss,
    # each at face value — callers sum per-layer auxes into the total loss
    # with no further multiply (so disabling one term never zeroes the other)
    aux = aux_loss_coef * routing.aux_loss
    if router_z_loss_coef:
        aux = aux + router_z_loss_coef * router_z_loss(router_logits)

    # dispatch: (N,E,C) × (N,D) → (E,C,D)
    expert_in = jnp.einsum(
        "nec,nd->ecd", routing.dispatch.astype(compute_dtype), tokens.astype(compute_dtype)
    )
    gate = jnp.einsum("ecd,edi->eci", expert_in, w_gate.astype(compute_dtype))
    up = jnp.einsum("ecd,edi->eci", expert_in, w_up.astype(compute_dtype))
    act = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("eci,eid->ecd", act, w_down.astype(compute_dtype))
    # combine: (N,E,C) × (E,C,D) → (N,D)
    out = jnp.einsum("nec,ecd->nd", routing.combine.astype(compute_dtype), expert_out)
    return out.reshape(b, s, d), aux


# ------------------------------------------------------------ dropless experts
def _keep_best_groups(scores, n_group: int, topk_group: int):
    """``scores`` (N, E) with every expert outside a row's ``topk_group`` best
    groups at ``-inf``: the ``E`` experts lie in ``n_group`` groups of equal
    size, side by side; a group's score is the sum of its two best scores (the
    DeepSeek-V3 rule), and ties go to the lower group as they go to the lower
    expert."""
    n, e = scores.shape
    grouped = scores.reshape(n, n_group, e // n_group)
    group_scores = jnp.sum(jax.lax.top_k(grouped, min(2, e // n_group))[0], axis=-1)
    _, kept = jax.lax.top_k(group_scores, topk_group)  # (N, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, e)


def route_sigmoid_topk(x, router_kernel, expert_bias, num_selected: int, *,
                       norm_topk: bool = True, norm_eps: float = 1e-6,
                       scale: float = 1.0, n_group: int = 1, topk_group: int = 1):
    """Sigmoid routing in float32: ``s = sigmoid(x @ W_r)``; the chosen experts
    are the top-k of ``s + expert_bias`` (the bias takes part in the choice
    only), with ``n_group > 1`` among the experts of a row's ``topk_group`` best
    groups only (:func:`_keep_best_groups`); the weights are ``s`` of the
    chosen, renormalised over them (``/ (sum + norm_eps)``) when ``norm_topk``,
    times ``scale``. ``x`` (N, D) -> ``(experts (N, k) int32, weights (N, k)
    float32)``. A row's routing depends on that row alone."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    choose_by = scores if expert_bias is None else scores + expert_bias.astype(jnp.float32)
    if n_group > 1:
        choose_by = _keep_best_groups(choose_by, n_group, topk_group)
    _, experts = jax.lax.top_k(choose_by, num_selected)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + norm_eps)
    return experts.astype(jnp.int32), weights * scale


def _head_pairs(pairs: int, held: int, routed: int) -> int:
    """How many of the ``pairs`` (row, choice) pairs, sorted held experts first,
    the grouped matmuls are handed when ``held`` of ``routed`` experts are here:
    twice the held experts' even share, in whole row tiles of 128; all of them
    where that is all or most of them anyway (every expert held, a decode
    step's few pairs). A prefill of 1,024 positions that chose 8 of 192 with 8
    held has 8,192 pairs, about 341 of them held: past those the kernel only
    writes zeros, three times a layer, and every pass between the matmuls runs
    over the rows of no held expert."""
    head = -(-2 * pairs * held // (routed * 128)) * 128
    return pairs if 2 * head > pairs else head


def dropless_moe(
    x: jax.Array,
    router_kernel: jax.Array,
    expert_bias: Optional[jax.Array],
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    *,
    num_selected: int,
    first: int = 0,
    layer=None,
    norm_topk: bool = True,
    norm_eps: float = 1e-6,
    scale: float = 1.0,
    n_group: int = 1,
    topk_group: int = 1,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array]:
    """SwiGLU experts without capacity and without drops: every row reaches the
    ``num_selected`` experts it chose.

    ``x`` (N, D); ``router_kernel`` (D, E) and ``expert_bias`` (E,) span ALL
    ``E`` experts; ``w1``/``w3`` (G, D, I) and ``w2`` (G, I, D) are the ``G``
    experts held here, experts ``first .. first + G - 1`` (default: all of
    them). Routing runs over all ``E`` (``n_group``, ``topk_group``,
    ``norm_eps``: :func:`route_sigmoid_topk`'s); the result is the part the held
    experts give, so the shares of a layer split over chips add up to the whole
    layer.
    With ``layer`` (an int or a traced scalar) the weights are every layer's,
    stacked on a leading axis, ``(L, G, ...)``: they are handed to the grouped
    matmul whole, as ``L * G`` groups of which only this layer's hold rows,
    because a slice of a stacked operand of a kernel is a copy of it (PERF.md,
    PR 30).

    The (row, expert) pairs are sorted by expert, held experts first, and the
    three matmuls are :func:`~.grouped_matmul.grouped_matmul` over the groups:
    one ``moe_gmm`` Pallas kernel each (interpreted off the TPU), its tiles
    chosen from the rows and widths at hand, the groups' visits computed once
    and shared by the three; differentiated, they are ``jax.lax.ragged_dot``.
    Where a share of the experts is held and the pairs are many, the matmuls
    and the passes between them take the head of the sorted pairs alone
    (:func:`_head_pairs`: twice the held experts' even share), and all of them
    in the batch that holds more than that; the result is the same either way.
    A row's result is
    computed from that row alone (its dot products, then its k parts summed
    in the order of its own choice): it does not depend on what else is in
    the batch.

    The router reads ``x`` as it comes (hand it float32 and no near-tie is
    decided by rounding); the experts read it in ``compute_dtype`` and sum in
    float32. Returns ``(out (N, D) in x's dtype, rows (E,) int32)``: ``rows``
    counts the rows every one of the ``E`` experts got, held here or not."""
    n, d = x.shape
    e = router_kernel.shape[1]
    stacked = layer is not None
    g = w1.shape[1] if stacked else w1.shape[0]
    k = num_selected
    experts, weights = route_sigmoid_topk(
        x, router_kernel, expert_bias, k, norm_topk=norm_topk, norm_eps=norm_eps,
        scale=scale, n_group=n_group, topk_group=topk_group,
    )
    flat = experts.reshape(n * k)
    rows = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    # held experts become 0..g-1 and sort first; the others follow, in no group
    local = (flat - first) % e
    order = jnp.argsort(local, stable=True)
    sizes = jnp.roll(rows, -first)[:g]  # the held experts' rows, in their local order
    if stacked:
        n_layers = w1.shape[0]
        w1, w3, w2 = (w.reshape(n_layers * g, *w.shape[2:]) for w in (w1, w3, w2))
    xc = x.astype(compute_dtype)

    def parts_of(pairs: int):
        """The held experts' products of the first ``pairs`` sorted pairs, every
        held one among them, back in the order of (row, choice)."""
        # which expert multiplies which rows: made once, the three matmuls share it
        visits = group_visits(sizes, pairs, layer * g if stacked else 0)
        head = order if pairs == n * k else order[:pairs]
        xs = xc[head // k]  # (pairs, D), sorted by expert

        def grouped(lhs, rhs):
            return grouped_matmul(lhs, rhs.astype(compute_dtype), visits)

        hidden = (jax.nn.silu(grouped(xs, w1)) * grouped(xs, w3)).astype(compute_dtype)
        # (pairs, D) float32; rows past the last group (experts held elsewhere) are zero
        parts = grouped(hidden, w2)
        back = jnp.argsort(order)
        # a head is taken only where its last row is no group's, so zero: every
        # pair behind the head reads that row
        return parts[back if pairs == n * k else jnp.minimum(back, pairs - 1)]

    # With a share of the experts held, most sorted pairs are no group's: the
    # matmuls take the head of them that holds every held pair at any routing
    # near even, and all of them only when a batch crowds onto the held experts
    head_pairs = _head_pairs(n * k, g, e)
    if head_pairs == n * k:
        parts = parts_of(n * k)
    else:
        parts = jax.lax.cond(
            jnp.sum(sizes) < head_pairs, lambda: parts_of(head_pairs), lambda: parts_of(n * k)
        )
    parts = parts.reshape(n, k, d)  # back to (row, choice)
    out = jnp.sum(parts * weights[:, :, None], axis=1)
    return out.astype(x.dtype), rows
