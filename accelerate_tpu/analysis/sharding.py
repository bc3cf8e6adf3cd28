"""graftcheck Level 3: static SPMD sharding & HBM audit of the hot programs.

Level 1 checks *what programs exist* (count, donation, callbacks); this
level checks *how they are laid out*. It AOT-lowers the same real programs
— the fused train step across the parallelism variants of
``parallelism_config.py`` (pure DP, FSDP, FSDP×TP, hybrid DCN-replicated
HSDP) and the slot engine's prefill/decode/verify per backend — and audits
the prepared shardings, the GSPMD-partitioned HLO, and XLA's static memory
analysis without executing anything. The two source papers' key artifacts
(arXiv 2004.13336: per-tensor weight-update layouts; arXiv 2112.01075:
reshard collectives are explicit in the lowered program) are exactly what
this pass reads.

Rules (program-scoped; waivers live in ``runs/sharding_baseline.json``
because there is no source line to comment on):

  G201  a large param / optimizer-moment / KV-arena leaf is fully
        replicated while the active ParallelismConfig claims that state is
        sharded (fsdp axes active or tp enabled) — the ZeRO regression
        class: opt state silently falling back to replicated costs
        2x-per-moment HBM on every chip
  G202  a GSPMD-inserted reshard collective (all-gather / all-to-all /
        collective-permute) communicates over a mesh axis the declared
        specs (``parallel.sharding.IMPLIED_RESHARD_AXES``) never imply for
        that op — an involuntary reshard the model code did not ask for
  G203  the static per-device HBM footprint (arguments + temps from XLA's
        memory analysis; donated outputs alias their inputs) grew past the
        per-program budget committed in ``runs/sharding_baseline.json``
        — growth fails, shrinkage passes, ``--update-baseline``
        re-baselines, mirroring G004
  G204  a collective crosses the slow DCN axis
        (``ParallelismConfig.dcn_axis_names``) inside a while-loop body —
        trip-count-weighted per-layer DCN traffic is the multi-slice
        scaling killer
  G205  a large non-donated input whose shape/dtype matches an unclaimed
        output — the buffer is dead after the call and donating it would
        have saved its HBM

Everything runs on the CPU backend with virtual devices, same as Level 1:
sharding annotations, replica groups, and memory analysis are
backend-independent artifacts of partitioning, not execution.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from . import Finding
from .lowering import (
    aliased_input_indices,
    atomic_write_json,
    compile_and_extract_spmd,
    flat_in_avals,
    groups_mesh_axes,
    iter_collectives,
    memory_table,
    mesh_device_coords,
)

BASELINE_PATH = os.path.join("runs", "sharding_baseline.json")

# Mirror of infer_shardings' min_weight_size: leaves below this many
# elements are deliberately left replicated (norm scales, biases), so G201
# must not flag them.
MIN_SHARDED_SIZE = 2 ** 10

# G205 floor: donation bookkeeping below 1 MiB is noise, not HBM.
MIN_DONATION_BYTES = 1 << 20

# Default slack before G203 calls HBM growth a regression. XLA's temp
# accounting moves a little across scheduler decisions; real regressions
# (an undonated duplicate of params, a replicated moment) are way past 2%.
HBM_TOLERANCE = 0.02


@dataclasses.dataclass
class StateLeaf:
    """One prepared state tensor with its claimed layout."""

    kind: str        # "param" | "moment" | "kv"
    path: str        # tree path, "model/embed_tokens/embedding"
    shape: tuple
    size: int        # elements
    nbytes: int
    axes: frozenset  # mesh axes the prepared spec shards over ({} = replicated)


@dataclasses.dataclass
class ShardedProgram:
    """One lowered hot program plus the layout metadata Level 3 audits."""

    name: str                 # "train.fsdp8/fused_train_step", "engine.paged/decode_step"
    source: str               # file findings point at
    lowered: Any              # jax.stages.Lowered
    mesh: Any = None          # jax Mesh (None for single-device engine programs)
    claims: frozenset = frozenset()   # axes the config claims state is sharded over
    dcn_axes: tuple = ()              # ParallelismConfig.dcn_axis_names
    state_leaves: List[StateLeaf] = dataclasses.field(default_factory=list)
    donated: Set[int] = dataclasses.field(default_factory=set)
    donated_optional: Set[int] = dataclasses.field(default_factory=set)
    # flat non-donated indices where NOT donating is the design (the
    # engine's carried ring must outlive the call; params are shared by
    # every program; host-refreshed tables are re-uploaded) — G205 skips.
    nondonate_ok: Set[int] = dataclasses.field(default_factory=set)
    out_leaves: List[Tuple[tuple, str]] = dataclasses.field(default_factory=list)
    _compiled: Any = dataclasses.field(default=None, repr=False)
    _hlo: Any = dataclasses.field(default=None, repr=False)
    _dumped: bool = dataclasses.field(default=False, repr=False)

    @property
    def multi_device(self) -> bool:
        return self.mesh is not None and any(
            s > 1 for s in self.mesh.shape.values()
        )

    def compile(self, want_dump: bool):
        """Compile once per record; the SPMD dump is only requested for
        multi-device programs (single-device modules have no partitioning
        pass to dump)."""
        if self._compiled is None or (want_dump and not self._dumped):
            self._compiled, self._hlo = compile_and_extract_spmd(
                self.lowered, prefix="graftcheck_shard_", want_dump=want_dump
            )
            self._dumped = want_dump
        return self._compiled, self._hlo


# --------------------------------------------------------------------------
# program builders
# --------------------------------------------------------------------------

# The fused train step under each parallelism claim worth auditing: pure
# replication (claims nothing — the G201 control), the FSDP path Level 1
# baselines, FSDP×TP composition, and hybrid DCN-replicated HSDP (the only
# variant with a declared DCN axis, so the only one G204 bites on).
TRAIN_VARIANTS: Tuple[Tuple[str, dict], ...] = (
    ("train.dp8", dict(dp_replicate_size=8)),
    ("train.fsdp8", dict(dp_shard_size=8)),
    ("train.tp2", dict(dp_shard_size=4, tp_size=2)),
    ("train.hsdp2x4",
     dict(dp_replicate_size=2, dp_shard_size=4, hybrid_dcn_replicate=True)),
)

_TRAIN_SOURCE = os.path.join("accelerate_tpu", "accelerator.py")


def _leaves_of(tree, kind: str) -> List[StateLeaf]:
    import jax
    import numpy as np

    from ..parallel.sharding import path_of, spec_used_axes

    out: List[StateLeaf] = []
    for key_path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        size = int(np.prod(shape)) if shape else 1
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        axes = frozenset(spec_used_axes(spec)) if spec is not None else frozenset()
        out.append(StateLeaf(
            kind=kind, path=path_of(key_path), shape=shape, size=size,
            nbytes=size * dtype.itemsize, axes=axes,
        ))
    return out


def _out_leaves(out_info) -> List[Tuple[tuple, str]]:
    import jax

    return [
        (tuple(o.shape), str(getattr(o, "dtype", "")))
        for o in jax.tree_util.tree_leaves(out_info)
    ]


def build_train_variant(tag: str, cfg_kwargs: dict) -> ShardedProgram:
    """Lower the real fused train step shape-only under one
    ParallelismConfig — same abstract-prepare path as Level 1's
    ``build_train_step_program``, parameterized by variant."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    from .lowering import leaf_count

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    try:
        cfg = ParallelismConfig(**cfg_kwargs)
        acc = Accelerator(parallelism_config=cfg)
        model = create_llama(LlamaConfig.tiny(num_hidden_layers=2), abstract=True)
        model, opt = acc.prepare(model, optax.adamw(1e-3, mu_dtype=jnp.bfloat16))
        model.policy = None
        step = acc.train_step(llama_loss, max_grad_norm=1.0)
        batch = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        lowered = step.lower(batch)
        p = leaf_count(model.params)
        o = leaf_count(opt.opt_state)
        claims: Set[str] = set(cfg.fsdp_dim_names)
        if cfg.tp_enabled:
            claims.add("tp")
        return ShardedProgram(
            name=f"{tag}/fused_train_step",
            source=_TRAIN_SOURCE,
            lowered=lowered,
            mesh=acc.state.mesh,
            claims=frozenset(claims),
            dcn_axes=cfg.dcn_axis_names,
            state_leaves=(_leaves_of(model.params, "param")
                          + _leaves_of(opt.opt_state, "moment")),
            donated=set(range(p + o)),
            out_leaves=_out_leaves(lowered.out_info),
        )
    finally:
        for s in (AcceleratorState, GradientState, PartialState):
            s._reset_state()


def build_engine_sharded(groups: Optional[Sequence[str]] = None) -> List[ShardedProgram]:
    """Wrap Level 1's engine traces with the layout metadata Level 3
    needs. Engines run single-device here, so G201/G202/G204 are vacuous
    (claims empty, no mesh); what these records feed is G203's per-program
    HBM budget and the KV-arena static estimate the drift test compares
    against ``engine.stats()``."""
    from .program import build_engine_programs

    out: List[ShardedProgram] = []
    for rec in build_engine_programs(groups):
        # in_avals is (positional_args, ...); the engine's donated dict
        # {"cache": ..., "pos": ..., "key": ...} is the first positional arg
        first = rec.lowered.in_avals[0] if rec.lowered.in_avals else None
        if isinstance(first, (tuple, list)) and first:
            first = first[0]
        kv_leaves: List[StateLeaf] = []
        if isinstance(first, dict) and "cache" in first:
            kv_leaves = _leaves_of(first["cache"], "kv")
        n_inputs = len(flat_in_avals(rec.lowered))
        out_leaves = []
        if rec.jaxpr is not None:
            out_leaves = [
                (tuple(av.shape), str(av.dtype))
                for av in rec.jaxpr.out_avals
            ]
        suffix = f".{rec.variant}" if getattr(rec, "variant", "") else ""
        out.append(ShardedProgram(
            name=f"{rec.group}/{rec.name}{suffix}",
            source=rec.source,
            lowered=rec.lowered,
            state_leaves=kv_leaves,
            donated=set(rec.donated),
            donated_optional=set(rec.donated_optional),
            # carried ring outlives the call by design; params are shared
            # across prefill/decode/verify; block tables are host-refreshed
            nondonate_ok=set(range(n_inputs)) - set(rec.donated),
            out_leaves=out_leaves,
        ))
    return out


def build_sharded_programs(
    groups: Optional[Sequence[str]] = None,
) -> List[ShardedProgram]:
    wanted = set(groups) if groups is not None else None
    records: List[ShardedProgram] = []
    for tag, kwargs in TRAIN_VARIANTS:
        if wanted is None or tag in wanted:
            records.append(build_train_variant(tag, kwargs))
    engine_groups = (
        None if wanted is None
        else [g for g in wanted if g.startswith("engine.")]
    )
    if engine_groups is None or engine_groups:
        records.extend(build_engine_sharded(engine_groups))
    return records


def pallas_static_table(rec: ShardedProgram, table: dict) -> dict:
    """Model correction for the ``engine.paged_pallas`` decode/verify
    programs' G203 tables. The CPU proxy lowers them in interpret mode,
    where the Pallas grid is a plain XLA loop staging its per-layer dense
    context through HBM temps; on TPU those block operands stream through
    VMEM and the dense (slots, max_len) context the reference op gathers
    is never materialized. The committed table must describe the TPU
    program, so the per-layer dense-context staging bytes (derived from
    the pool leaves' own shapes — pure arithmetic, same spirit as G503's
    padding model) are subtracted from the measured temps."""
    if not rec.name.startswith("engine.paged_pallas/"):
        return table
    if not rec.name.endswith(("/decode_step", "/verify_step")):
        return table
    import math

    from .perf import ENGINE_MAX_LEN, ENGINE_SLOTS

    staged = 0
    for leaf in rec.state_leaves:
        # pool leaf (L, num_blocks, block_size, *feature): one layer's
        # dense per-slot context = slots * max_len * feature elements
        if leaf.kind != "kv" or len(leaf.shape) < 3:
            continue
        feature = math.prod(leaf.shape[3:]) if len(leaf.shape) > 3 else 1
        itemsize = leaf.nbytes // max(1, math.prod(leaf.shape))
        staged += ENGINE_SLOTS * ENGINE_MAX_LEN * feature * itemsize
    out = dict(table)
    out["temp_size_in_bytes"] = max(0, int(table["temp_size_in_bytes"]) - staged)
    out["hbm_live"] = max(0, int(table["hbm_live"]) - staged)
    return out


def static_kv_bytes(rec: ShardedProgram) -> int:
    """Static KV-arena footprint of an engine program — the number the
    runtime gauge ``engine.stats()['kv']['hbm_bytes']`` must agree with."""
    return sum(l.nbytes for l in rec.state_leaves if l.kind == "kv")


# --------------------------------------------------------------------------
# rules (pure functions over extracted facts — unit-testable without jax)
# --------------------------------------------------------------------------

def check_replication(
    name: str,
    source: str,
    leaves: Sequence[StateLeaf],
    claims: frozenset,
    min_size: int = MIN_SHARDED_SIZE,
) -> List[Finding]:
    """G201 — big state leaves replicated while the config claims sharding."""
    if not claims:
        return []
    findings = []
    for leaf in leaves:
        if leaf.size >= min_size and not leaf.axes:
            findings.append(Finding(
                "G201", source, 1,
                f"{name}: {leaf.kind} '{leaf.path}' {leaf.shape} "
                f"({leaf.nbytes}B) is fully replicated while the config "
                f"claims sharding over {sorted(claims)} — "
                f"{leaf.nbytes}B of HBM duplicated on every device",
                program=name,
            ))
    return findings


def check_reshards(
    name: str,
    source: str,
    instrs: Sequence[dict],
    axis_names: Sequence[str],
    coords_by_id: dict,
    implied: Optional[Dict[str, tuple]] = None,
) -> List[Finding]:
    """G202 — reshard collectives over axes the declared specs never imply."""
    if implied is None:
        from ..parallel.sharding import IMPLIED_RESHARD_AXES as implied
    findings = []
    for rec in instrs:
        allowed = implied.get(rec["op"])
        if allowed is None:  # reductions are not reshard evidence
            continue
        axes = groups_mesh_axes(rec.get("groups"), axis_names, coords_by_id)
        extra = sorted(axes - set(allowed))
        if not extra:
            continue
        where = rec.get("source") or rec.get("op_name") or rec.get("comp", "")
        findings.append(Finding(
            "G202", source, 1,
            f"{name}: implicit reshard — {rec['op']} over undeclared mesh "
            f"ax{'es' if len(extra) > 1 else 'is'} {extra} "
            f"(operand {rec.get('operand', '?')}, {rec['bytes']}B"
            f"{' x%d' % rec['multiplier'] if rec.get('multiplier', 1) > 1 else ''}"
            f"{', ' + where if where else ''}) — declared specs imply "
            f"{rec['op']} only on {sorted(allowed)}",
            program=name,
        ))
    return findings


def check_dcn_loops(
    name: str,
    source: str,
    instrs: Sequence[dict],
    axis_names: Sequence[str],
    coords_by_id: dict,
    dcn_axes: Sequence[str],
) -> List[Finding]:
    """G204 — trip-weighted collectives crossing the DCN axis in a loop."""
    if not dcn_axes:
        return []
    findings = []
    for rec in instrs:
        if rec.get("multiplier", 1) <= 1:
            continue  # not inside a while body
        axes = groups_mesh_axes(rec.get("groups"), axis_names, coords_by_id)
        crossing = sorted(axes & set(dcn_axes))
        if not crossing:
            continue
        where = rec.get("source") or rec.get("op_name") or rec.get("comp", "")
        findings.append(Finding(
            "G204", source, 1,
            f"{name}: {rec['op']} crosses DCN ax{'es' if len(crossing) > 1 else 'is'} "
            f"{crossing} inside a while body — x{rec['multiplier']} per step, "
            f"{rec['bytes']}B each ({rec['bytes'] * rec['multiplier']}B/step"
            f"{', ' + where if where else ''}) — hoist it out of the loop or "
            f"keep per-layer traffic on ICI",
            program=name,
        ))
    return findings


def check_missed_donation(
    name: str,
    source: str,
    in_leaves: Sequence[Any],
    out_leaves: Sequence[Tuple[tuple, str]],
    donated: Set[int],
    donated_optional: Set[int],
    nondonate_ok: Set[int],
    aliased: Dict[int, int],
    min_bytes: int = MIN_DONATION_BYTES,
) -> List[Finding]:
    """G205 — big non-donated inputs whose buffers die inside the call.

    A non-donated input with a same-shape/dtype output that no donated
    input already claims could have been donated: after the call the old
    buffer is garbage, but XLA had to allocate the output fresh — the
    missed donation wastes exactly that many HBM bytes at peak."""
    import numpy as np
    from collections import Counter

    def key(shape, dtype):
        return (tuple(shape), str(np.dtype(dtype)))

    avail = Counter(key(s, d) for s, d in out_leaves)
    # outputs consumed by actually-donated (aliased) inputs are spoken for
    for i in aliased:
        if 0 <= i < len(in_leaves):
            k = key(in_leaves[i].shape, in_leaves[i].dtype)
            if avail[k] > 0:
                avail[k] -= 1
    findings = []
    for i, av in enumerate(in_leaves):
        if (i in donated or i in donated_optional or i in nondonate_ok
                or i in aliased):
            continue
        shape = tuple(getattr(av, "shape", ()))
        size = int(np.prod(shape)) if shape else 1
        nbytes = size * np.dtype(getattr(av, "dtype", np.float32)).itemsize
        if nbytes < min_bytes:
            continue
        k = key(shape, getattr(av, "dtype", np.float32))
        if avail[k] > 0:
            avail[k] -= 1
            findings.append(Finding(
                "G205", source, 1,
                f"{name}: non-donated flat input {i} {shape} ({nbytes}B) is "
                "dead after the call and an output of the same shape/dtype "
                "exists — donate it (donate_argnums / donate_argnames) to "
                f"save {nbytes}B of peak HBM",
                program=name,
            ))
    return findings


def compare_hbm(
    observed: Dict[str, dict],
    baseline: Dict[str, Any],
    baseline_path: str = BASELINE_PATH,
) -> List[Finding]:
    """G203 — per-program static HBM vs the committed budget. Growth past
    the tolerance fails; shrinkage always passes (and is picked up by the
    next --update-baseline)."""
    findings: List[Finding] = []
    budgets = baseline.get("hbm", {})
    tol = float(baseline.get("tolerance", HBM_TOLERANCE))
    for name, table in sorted(observed.items()):
        budget = budgets.get(name)
        if budget is None:
            findings.append(Finding(
                "G203", baseline_path, 1,
                f"{name}: no HBM budget committed — re-baseline with "
                "`python -m accelerate_tpu.analysis --update-baseline`",
                program=name,
            ))
            continue
        live = int(table.get("hbm_live", 0))
        limit = int(budget.get("hbm_live", 0))
        if live > limit * (1.0 + tol):
            findings.append(Finding(
                "G203", baseline_path, 1,
                f"{name}: static per-device HBM grew to {live}B vs the "
                f"{limit}B budget (+{live - limit}B, "
                f"{(live - limit) * 100.0 / max(limit, 1):.1f}% > "
                f"{tol * 100:.0f}% tolerance) — args "
                f"{table.get('argument_size_in_bytes', 0)}B + temps "
                f"{table.get('temp_size_in_bytes', 0)}B; fix the regression "
                "or re-baseline deliberately",
                program=name,
            ))
    return findings


# --------------------------------------------------------------------------
# baseline + waivers
# --------------------------------------------------------------------------

def load_sharding_baseline(path: str = BASELINE_PATH) -> Optional[Dict[str, Any]]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def make_sharding_baseline(
    observed: Dict[str, dict],
    previous: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """New baseline from observed memory tables. Waivers and tolerance are
    REVIEWED content, not measurements — re-baselining preserves them."""
    prev = previous or {}
    return {
        "hbm": {
            name: {k: v for k, v in table.items()
                   if k != "generated_code_size_in_bytes"}
            for name, table in sorted(observed.items())
        },
        "tolerance": prev.get("tolerance", HBM_TOLERANCE),
        "waivers": prev.get("waivers", {}),
    }


def apply_waivers(
    findings: Sequence[Finding],
    baseline: Optional[Dict[str, Any]],
) -> Tuple[List[Finding], int]:
    """Drop findings matched by the baseline's waiver table.

    ``baseline["waivers"]`` maps rule code -> {regex: reason}; the regex is
    searched against ``"<program> <message>"`` so one entry can pin a
    single collective ("train.tp2.*collective-permute.*tp") or a whole
    program. Reasons are mandatory documentation — the reviewable analog
    of the host lint's ``# graft: xxx-ok — why`` comments."""
    waivers = (baseline or {}).get("waivers", {})
    if not waivers:
        return list(findings), 0
    compiled = {
        code: [(re.compile(pat), reason) for pat, reason in pats.items()]
        for code, pats in waivers.items()
    }
    kept: List[Finding] = []
    waived = 0
    for f in findings:
        subject = f"{f.program} {f.message}"
        if any(pat.search(subject) for pat, _ in compiled.get(f.code, ())):
            waived += 1
            continue
        kept.append(f)
    return kept, waived


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def observe_hbm(
    records: Sequence[ShardedProgram], with_collectives: bool = True,
) -> Dict[str, dict]:
    """name -> memory table for every record (compiles as a side effect)."""
    observed = {}
    for rec in records:
        want_dump = with_collectives and rec.multi_device
        compiled, _hlo = rec.compile(want_dump)
        observed[rec.name] = pallas_static_table(rec, memory_table(compiled))
    return observed


def run_sharding_checks(
    baseline_path: str = BASELINE_PATH,
    update_baseline: bool = False,
    groups: Optional[Sequence[str]] = None,
    with_collectives: bool = True,
    baseline_sink: Optional[list] = None,
) -> List[Finding]:
    records = build_sharded_programs(groups)
    findings: List[Finding] = []
    observed: Dict[str, dict] = {}

    for rec in records:
        findings.extend(check_replication(
            rec.name, rec.source, rec.state_leaves, rec.claims,
        ))
        aliased = aliased_input_indices(rec.lowered.as_text())
        findings.extend(check_missed_donation(
            rec.name, rec.source, flat_in_avals(rec.lowered), rec.out_leaves,
            rec.donated, rec.donated_optional, rec.nondonate_ok, aliased,
        ))
        want_dump = with_collectives and rec.multi_device
        compiled, hlo = rec.compile(want_dump)
        observed[rec.name] = pallas_static_table(rec, memory_table(compiled))
        if want_dump and hlo:
            instrs, _notes = iter_collectives(hlo, rec.mesh.size)
            axis_names = tuple(rec.mesh.axis_names)
            coords = mesh_device_coords(rec.mesh)
            findings.extend(check_reshards(
                rec.name, rec.source, instrs, axis_names, coords,
            ))
            findings.extend(check_dcn_loops(
                rec.name, rec.source, instrs, axis_names, coords,
                rec.dcn_axes,
            ))

    baseline = load_sharding_baseline(baseline_path)
    if update_baseline:
        new = make_sharding_baseline(observed, previous=baseline)
        if baseline_sink is not None:
            baseline_sink.append((baseline_path, new))
        else:
            atomic_write_json(new, baseline_path)
        kept, _ = apply_waivers(findings, new)
        return kept
    if baseline is None:
        findings.append(Finding(
            "G203", baseline_path, 1,
            "sharding baseline missing — generate it with "
            "`python -m accelerate_tpu.analysis --update-baseline`",
        ))
        kept, _ = apply_waivers(findings, None)
        return kept
    findings.extend(compare_hbm(observed, baseline, baseline_path))
    kept, _ = apply_waivers(findings, baseline)
    return kept
