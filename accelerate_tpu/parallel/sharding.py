"""Sharding-rule engine: from ParallelismConfig + param pytree to per-leaf
NamedShardings.

This is the TPU-native replacement for the reference's entire strategy-plugin
layer (SURVEY §2.4): where the reference wraps models in DDP /
FSDP.fully_shard / DTensor TP plans (accelerator.py:1877-2050,
utils/fsdp_utils.py:741-903), GSPMD needs only a PartitionSpec per parameter —
XLA inserts the all-gathers/reduce-scatters/all-reduces.

Rules are ``(regex, PartitionSpec)`` pairs matched against ``/``-joined
parameter paths (the Megatron/maxtext idiom). Unmatched parameters fall back
to the FSDP heuristic: shard the largest dim divisible by the fsdp-axes size
when the parameter is big enough, else replicate.
"""

from __future__ import annotations

import contextlib as _contextlib
import contextvars as _contextvars
import re
from typing import Any, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "path_of",
    "infer_shardings",
    "replicated",
    "apply_shardings",
    "shard_params",
    "spec_used_axes",
    "ShardingRule",
    "IMPLIED_RESHARD_AXES",
]

ShardingRule = tuple[str, P]

# Which mesh axes each RESHAPE collective is implied on by the specs this
# module declares — the contract graftcheck G202 audits the lowered HLO
# against. (all-reduce / reduce-scatter are REDUCTIONS, implied wherever a
# contraction crosses an axis, so they are not reshard evidence and are
# deliberately absent.)
#
#   all-gather          fsdp storage→use gathers (gather_over_fsdp),
#                       Megatron-SP sequence re-gathers at block entry
#                       (constrain_activation "residual"→"heads"), sp/cp
#                       sequence assembly
#   all-to-all          Ulysses head<->sequence exchange on sp, and the
#                       Megatron-SP seq-shard→head-shard transition on tp
#                       (the "residual"→"heads" constraint pair lowers to
#                       an a2a over tp — cheaper than gather+slice)
#   collective-permute  ring context-parallel block rotation (cp) and
#                       pipeline-stage boundary shifts (pp)
#
# A lowered program containing one of these ops over any OTHER >1 mesh axis
# means GSPMD invented a reshard the declared specs never asked for —
# exactly the "involuntary full rematerialization" class the activation
# anchors below exist to prevent. (GSPMD sometimes DECOMPOSES a declared
# gather into an a2a+permute pair — arXiv 2112.01075's portable
# redistribution — those known sites carry documented waivers in
# runs/sharding_baseline.json rather than a blanket allowance here.)
IMPLIED_RESHARD_AXES = {
    "all-gather": ("dp_shard", "tp", "sp", "cp"),
    "all-to-all": ("sp", "tp"),
    "collective-permute": ("cp", "pp"),
}


def path_of(key_path) -> str:
    """Join a jax tree key-path into 'a/b/c' form."""
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def spec_used_axes(spec: P) -> set:
    """Mesh axes a PartitionSpec actually shards over (flattened through
    tuple entries). Empty set = fully replicated — the predicate graftcheck
    G201 applies to every prepared param/moment leaf."""
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, str):
            used.add(entry)
        else:
            used.update(entry)
    return used


_spec_used_axes = spec_used_axes


def _norm_spec(spec: P) -> P:
    """Strip trailing Nones: ``P(None, 'x', None)`` and ``P(None, 'x')``
    shard identically, but pjit's executable cache keys on the spec as
    written — a prepare-time sharding with a trailing None vs the same
    sharding as a jit output (jax normalizes those) would recompile the
    whole fused train step on its second call
    (tests/test_accelerator.py::test_train_step_compiles_once_sharded)."""
    entries = list(spec)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _fsdp_spec_for(shape, mesh, fsdp_axes, base_spec: Optional[P] = None) -> P:
    """Shard the largest not-yet-sharded dim divisible by the fsdp-axes size.

    When ``base_spec`` already shards some dims (e.g. a TP rule), FSDP picks
    among the remaining dims — the GSPMD formulation of HSDP/TP+FSDP
    composition (reference fsdp_utils.py:770 mesh kwarg)."""
    n = _axes_size(mesh, fsdp_axes)
    if n <= 1:
        return base_spec if base_spec is not None else P()
    entries = list(base_spec) if base_spec is not None else []
    entries += [None] * (len(shape) - len(entries))
    candidates = [
        (dim_size, i)
        for i, dim_size in enumerate(shape)
        if entries[i] is None and dim_size % n == 0 and dim_size >= n
    ]
    if not candidates:
        return base_spec if base_spec is not None else P()
    _, dim = max(candidates)
    axes_entry = fsdp_axes[0] if len(fsdp_axes) == 1 else tuple(fsdp_axes)
    entries[dim] = axes_entry
    return _norm_spec(P(*entries))


def infer_shardings(
    params: Any,
    mesh: Mesh,
    rules: Optional[Sequence[ShardingRule]] = None,
    fsdp_axes: Sequence[str] = (),
    min_weight_size: int = 2**10,
    fsdp_compose_with_rules: bool = True,
) -> Any:
    """Infer a NamedSharding for every leaf of ``params``.

    Order of precedence per leaf:
      1. first matching ``(regex, PartitionSpec)`` rule (searched, not
         fullmatch — use anchors for precision);
      2. [+ optionally composed with] the FSDP largest-dim heuristic when
         ``fsdp_axes`` are active and ``leaf.size >= min_weight_size``;
      3. replicated.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in (rules or [])]
    fsdp_active = bool(fsdp_axes) and _axes_size(mesh, fsdp_axes) > 1

    def leaf_sharding(key_path, leaf):
        shape = getattr(leaf, "shape", ())
        path = path_of(key_path)
        base_spec = None
        for pat, spec in compiled:
            if pat.search(path):
                base_spec = spec
                break
        if fsdp_active and (np.prod(shape) if shape else 0) >= min_weight_size:
            if base_spec is None:
                return NamedSharding(mesh, _fsdp_spec_for(shape, mesh, fsdp_axes))
            if fsdp_compose_with_rules and not (_spec_used_axes(base_spec) & set(fsdp_axes)):
                return NamedSharding(mesh, _fsdp_spec_for(shape, mesh, fsdp_axes, base_spec))
        if base_spec is not None:
            return NamedSharding(mesh, _norm_spec(base_spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def apply_shardings(params: Any, shardings: Any) -> Any:
    """Place (or re-place) every leaf according to its sharding — the one-time
    "wrap" step of prepare() (vs the reference's module surgery).

    Abstract leaves (``jax.ShapeDtypeStruct``) are annotated instead of
    placed: prepare() then works shape-only, so a 7B-class config can be
    sharded, lowered, and compile-analyzed on a small host without ever
    materializing the parameters (see Accelerator.train_step's ``.lower``)."""

    def place(p, s):
        if isinstance(p, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=s)
        return jax.device_put(p, s)

    return jax.tree_util.tree_map(place, params, shardings)


def shard_params(
    params: Any,
    mesh: Mesh,
    rules: Optional[Sequence[ShardingRule]] = None,
    fsdp_axes: Sequence[str] = (),
    min_weight_size: int = 2**10,
) -> tuple[Any, Any]:
    """Convenience: infer + apply. Returns (sharded_params, shardings)."""
    shardings = infer_shardings(
        params, mesh, rules=rules, fsdp_axes=fsdp_axes, min_weight_size=min_weight_size
    )
    return apply_shardings(params, shardings), shardings


def sharding_summary(params: Any, shardings: Any) -> str:
    """Human-readable table of param path → shape → spec (debugging aid; the
    reference has no equivalent — module reprs serve this role there)."""
    lines = []

    def visit(key_path, leaf, sharding):
        lines.append(
            f"{path_of(key_path):60s} {str(tuple(getattr(leaf, 'shape', ()))):20s} "
            f"{str(sharding.spec)}"
        )

    jax.tree_util.tree_map_with_path(visit, params, shardings)
    return "\n".join(lines)


# ------------------------------------------------------- activation anchors
# Batch/sequence/feature mesh axes that activations shard over. Anchoring
# activations at block boundaries stops the SPMD partitioner from picking a
# different layout for the transpose (backward) program — without these, the
# FSDP×CP fused train step hits "Involuntary full rematerialization"
# replicate-and-reshard cliffs in the chunked-CE/MLP backward.
_ACT_BATCH_AXES = ("dp_replicate", "dp_shard")
_ACT_SEQ_AXES = ("cp", "sp")
_ACT_TP_AXIS = ("tp",)


def current_mesh() -> Optional[Mesh]:
    """The Accelerator's device mesh if one is live, else None. Peeks the
    Borg state without initializing it — model code must stay usable with
    plain jax.jit outside any Accelerator."""
    from ..state import AcceleratorState

    return AcceleratorState._shared_state.get("mesh")


def _axis_entry(mesh: Mesh, axes: Sequence[str], dim_size: int):
    """The subset of ``axes`` present in ``mesh`` with size>1, as a
    PartitionSpec entry — or None when nothing applies or ``dim_size`` isn't
    divisible (uneven activation sharding is never worth the padding)."""
    use = [a for a in axes if mesh.shape.get(a, 1) > 1]
    if not use:
        return None
    prod = int(np.prod([mesh.shape[a] for a in use]))
    if prod <= 1 or dim_size % prod != 0:
        return None
    return tuple(use) if len(use) > 1 else use[0]


def _in_manual_region() -> bool:
    """Inside a shard_map manual region (ring/Ulysses/pp internals), layout
    hints must stand down: constraining again is at best a no-op and on some
    backends a compiler crash. One probe shared by every hint site."""
    try:
        return bool(jax.sharding.get_abstract_mesh().manual_axes)
    except Exception:
        return False


# (fsdp_axes, min_weight_size) scoped to the model whose apply is running —
# set by Model._mp_apply so multi-model setups with different fsdp configs
# do not cross-pin (ADVICE r4: process-global "last prepare wins" hints).
_MODEL_FSDP_HINTS: _contextvars.ContextVar = _contextvars.ContextVar(
    "model_fsdp_hints", default=None
)


@_contextlib.contextmanager
def model_fsdp_hints(hints):
    """Scope per-model (fsdp_axes, min_weight_size) gather-pin hints for the
    duration of a model apply/trace. ``hints=None`` is a no-op passthrough."""
    if hints is None:
        yield
        return
    token = _MODEL_FSDP_HINTS.set(tuple(hints))
    try:
        yield
    finally:
        _MODEL_FSDP_HINTS.reset(token)


def _fsdp_use_hints(mesh: Mesh):
    """(active fsdp axes, min weight size) for use-time gather pinning.

    Resolution order: the per-model hints scoped by :func:`model_fsdp_hints`
    (Model._mp_apply enters it with the config THIS model was prepared
    under — so two models prepared with different fsdp configs each pin
    gathers to their own storage spec), then the live AcceleratorState
    (prepare_model records the last config — covers stage fns and other
    paths that bypass Model apply). Nothing recorded (bare shard_params /
    rules-only meshes) means NO storage pin: pinning a weight that is not
    actually fsdp-sharded would force a pointless reshard+gather round
    trip. Hints are a performance hint only — a stale hint can cost layout
    efficiency but never correctness, since sharding constraints never
    change values."""
    from ..state import AcceleratorState

    scoped = _MODEL_FSDP_HINTS.get()
    if scoped is not None:
        axes, minw = scoped
    else:
        st = AcceleratorState._shared_state
        axes = st.get("fsdp_axes") or ()
        minw = st.get("fsdp_min_weight_size", 2**10)
    return tuple(a for a in axes if mesh.shape.get(a, 1) > 1), minw


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_pin(w, storage_sh, use_sh):
    """Storage→use-layout reshard with a reduce-scatter backward.

    Forward: pin the (already compute-dtype) weight to its storage sharding
    — the cast runs on-shard — then release to the use layout, so the
    all-gather moves the compute dtype.

    Backward: constrain the cotangent ONLY to the storage sharding. The
    naive transpose would replay both constraints in reverse: the use-layout
    (replicated) constraint forces the partial weight-grad to materialize
    via a FULL all-reduce before the storage constraint slices it. Going
    straight from partial to shard is exactly reduce-scatter — half the ICI
    bytes per step on the FSDP grad path."""
    return jax.lax.with_sharding_constraint(
        jax.lax.with_sharding_constraint(w, storage_sh), use_sh
    )


def _gather_pin_fwd(w, storage_sh, use_sh):
    return _gather_pin(w, storage_sh, use_sh), None


def _gather_pin_bwd(storage_sh, use_sh, _, g):
    return (jax.lax.with_sharding_constraint(g, storage_sh),)


_gather_pin.defvjp(_gather_pin_fwd, _gather_pin_bwd)


def gather_over_fsdp(w, tp_dim: Optional[int] = None, mesh: Optional[Mesh] = None):
    """Use-time all-gather of a 2D fsdp-sharded weight: replicated on every
    axis except ``tp``, which stays on dim ``tp_dim`` when given and it
    divides (Megatron column sharding: tp_dim=1; row: tp_dim=0; None
    replicates fully).

    Call this on the weight AFTER casting to the compute dtype. GSPMD runs
    elementwise ops on their OUTPUT sharding, so a lone replication
    constraint on the cast would gather the f32 master weight and convert
    afterwards — 2x the ICI bytes. Two constraints fix the schedule: pin the
    cast to the weight's STORAGE sharding (cast runs on-shard), then release
    to the use-time layout (the all-gather moves bf16). The use-time
    constraint also keeps the weight's consumers on THEIR layout so the
    backward computes a local partial + psum for the weight grad instead of
    resharding the activation gradient (involuntary full rematerialization)."""
    if mesh is None:
        mesh = current_mesh()
    if mesh is None or getattr(w, "ndim", 0) != 2:
        return w
    if _in_manual_region():
        return w
    spec = [None, None]
    if tp_dim is not None:
        spec[tp_dim] = _axis_entry(mesh, _ACT_TP_AXIS, w.shape[tp_dim])
    try:
        fsdp_axes, minw = _fsdp_use_hints(mesh)
        use_spec = P(*spec)
        if fsdp_axes and int(np.prod(w.shape)) >= minw:
            storage = _fsdp_spec_for(
                w.shape, mesh, list(fsdp_axes),
                use_spec if any(spec) else None,
            )
            if _spec_used_axes(storage) - _spec_used_axes(use_spec):
                return _gather_pin(
                    w,
                    NamedSharding(mesh, storage),
                    NamedSharding(mesh, use_spec),
                )
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, use_spec))
    except Exception:
        return w


def replicate_over_fsdp(w, mesh: Optional[Mesh] = None, keep_tp: bool = True):
    """:func:`gather_over_fsdp` with the historical signature: ``keep_tp``
    keeps ``tp`` on the last (output) dim — column sharding."""
    return gather_over_fsdp(w, tp_dim=1 if keep_tp else None, mesh=mesh)


def constrain_activation(x, kind: str = "residual", mesh: Optional[Mesh] = None):
    """``with_sharding_constraint`` for a (B, S, ..., F) activation.

    kind: "residual" leaves the feature dim replicated (post-o_proj /
    post-down_proj block outputs); "intermediate" shards the feature dim over
    ``tp`` (gate/up MLP activations, Megatron column-parallel outputs);
    "vocab" likewise for logits. No-op when no mesh is live, inside fully
    manual shard_map regions, or when no named axis applies.

    Megatron sequence parallelism comes from the "residual" spec: between
    blocks the SEQUENCE dim is sharded over ``tp`` too (composing with
    cp/sp), so the partitioner turns each row-parallel matmul's output
    all-reduce into reduce-scatter + the next block's all-gather (half the
    TP bytes) and — the big one — saved-for-backward residuals shrink by
    the tp degree (without it a 70B model under tp=8 keeps every block's
    full-sequence residual on every chip and does not fit).
    Norms/elementwise between blocks run seq-sharded for free.
    """
    if mesh is None:
        mesh = current_mesh()
    if mesh is None or getattr(x, "ndim", 0) < 2:
        return x
    if _in_manual_region():
        return x
    batch = _axis_entry(mesh, _ACT_BATCH_AXES, x.shape[0])
    if kind == "heads" and x.ndim >= 4:
        # (B, S, H, D) entering attention: FULL sequence, heads over tp —
        # the Megatron-SP transition point. Without this anchor the
        # partitioner leaves q/k/v seq-sharded and re-gathers the sequence
        # INSIDE the kv-block scan (observed: one 512 MB all-gather per kv
        # block per layer in the 70B tp8 module — 2 TB/step). cp/sp keep
        # their sequence shard (the ring/Ulysses shard_map owns that
        # layout); only tp's share of the sequence is gathered here.
        heads = _axis_entry(mesh, _ACT_TP_AXIS, x.shape[-2])
        seq = _axis_entry(mesh, _ACT_SEQ_AXES, x.shape[1])
        if batch is None and heads is None and seq is None:
            return x
        entries = [batch, seq] + [None] * (x.ndim - 4) + [heads, None]
    else:
        seq = None
        if x.ndim >= 3:
            if kind == "residual" and mesh.shape.get("pp", 1) == 1:
                # Megatron-SP: tp joins the sequence axes ONLY where the
                # feature dim is replicated (one axis cannot appear on two
                # dims); fall back to cp/sp alone when the combined product
                # does not divide the sequence — dropping the pre-existing
                # cp/sp shard would be a memory/ICI REGRESSION, not just a
                # missed optimization. Disabled under pp meshes: the
                # seq-over-tp residual crossing the pipeline stage boundary
                # emits data-independent resharding permutes that race
                # XLA:CPU's thunk rendezvous (the known deadlock class) and
                # would be wasted ICI on TPU; SPxPP needs the stage layout
                # itself to carry the seq shard (future work).
                seq = _axis_entry(mesh, _ACT_SEQ_AXES + _ACT_TP_AXIS, x.shape[1])
            if seq is None:
                seq = _axis_entry(mesh, _ACT_SEQ_AXES, x.shape[1])
        feat = (
            _axis_entry(mesh, _ACT_TP_AXIS, x.shape[-1])
            if kind in ("intermediate", "vocab")
            else None
        )
        if batch is None and seq is None and feat is None:
            return x
        if x.ndim == 2:  # (B, F) — e.g. single-token decode logits
            entries = [batch, feat]
        else:
            entries = [batch, seq] + [None] * (x.ndim - 3) + [feat]
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*entries))
        )
    except Exception:
        # e.g. a shard_map region where these axes are manual — the anchor is
        # an optimization, never a correctness requirement
        return x
