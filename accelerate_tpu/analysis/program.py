"""graftcheck Level 1: program analysis over the registered jitted programs.

Builds the repo's REAL hot programs — the fused train step and the slot
engine's prefill_insert / decode_step / verify_step in each backend
configuration — at tiny shapes, then inspects the jaxprs and lowered
StableHLO for invariants that hold on the shipped tree:

  G001  no host callback / infeed / outfeed primitive inside a jitted
        program (a stray ``jax.debug.print`` or ``io_callback`` turns a
        fused step into a host round-trip per dispatch)
  G002  donation correctness: every donated input is aliased to an output
        (``tf.aliasing_output``) and NO non-donated input is aliased —
        donating the carried tree would invalidate the deferred-readback
        ring, and a donated-but-unaliased buffer silently doubles peak
        memory
  G003  no weak-typed (python-scalar) program operand — each distinct
        weak/strong promotion fragments the jit cache into an extra
        program
  G004  program-count + collective-inventory budget: the observed program
        set per configuration and the train step's collective inventory
        must not grow past ``runs/static_baseline.json`` (re-baseline
        explicitly with ``--update-baseline``)

Everything here works on the CPU backend with virtual devices: tracing
never executes, ``tf.aliasing_output`` attributes appear in CPU lowerings,
and the SPMD partitioner runs under ``--xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Set

from . import Finding
from .lowering import (
    aliased_input_indices,
    collect_primitives,
    compile_and_extract_spmd,
    is_forbidden_primitive,
    leaf_count,
    parse_collectives,
    weak_typed_inputs,
)

BASELINE_PATH = os.path.join("runs", "static_baseline.json")

# One engine configuration never needs more than prefill + decode + verify.
ENGINE_PROGRAM_CEILING = 3

# Where each program group's source lives (findings point here).
_GROUP_SOURCE = {
    "train_step": os.path.join("accelerate_tpu", "accelerator.py"),
    "engine.dense": os.path.join("accelerate_tpu", "engine.py"),
    "engine.spec": os.path.join("accelerate_tpu", "engine.py"),
    "engine.paged": os.path.join("accelerate_tpu", "engine.py"),
    # the Pallas flash-decode + fused-sampling variant (ops/paged_decode.py)
    "engine.paged_pallas": os.path.join("accelerate_tpu", "engine.py"),
    # lowered only by Level 5 (analysis/numerics.py): the int8 KV variant
    "engine.paged_int8": os.path.join("accelerate_tpu", "engine.py"),
    # chunked prefill + host-tier restore (docs/serving.md long-context)
    "engine.longctx": os.path.join("accelerate_tpu", "engine.py"),
}

_CALLBACK_CUSTOM_CALL_RE = re.compile(
    r"stablehlo\.custom_call\s+@(\w*(?:callback|infeed|outfeed)\w*)"
)


@dataclasses.dataclass
class ProgramRecord:
    """One lowered hot program plus the metadata the checks need."""

    group: str           # "train_step" | "engine.dense" | "engine.spec" | ...
    name: str            # "prefill_insert" | "decode_step" | ...
    lowered: Any         # jax.stages.Lowered
    donated: Set[int]    # flat input indices that MUST carry an alias
    jaxpr: Any = None    # ClosedJaxpr when tracing exposed one (engine path)
    # flat indices donated but legitimately droppable (jax strips donation
    # for inputs the program never reads). Allowed, not required, to alias.
    donated_optional: Set[int] = dataclasses.field(default_factory=set)
    # family member tag ("chunk"/"restore" for the chunked-prefill members
    # of prefill_insert): G004 counts families by `name`; the perf/HBM
    # levels key budgets by "<group>/<name>.<variant>" so each member gets
    # its own committed row
    variant: str = ""

    @property
    def source(self) -> str:
        return _GROUP_SOURCE.get(self.group, "accelerate_tpu")


# --------------------------------------------------------------------------
# program builders
# --------------------------------------------------------------------------

def _tiny_model():
    from accelerate_tpu.models.llama import LlamaConfig, create_llama

    return create_llama(LlamaConfig.tiny(num_hidden_layers=2), seed=0)


def _engine_records(group: str, engine, model) -> List[ProgramRecord]:
    """Trace the engine's jitted programs with the engine's own concrete
    state, mirroring the insert()/step() call sites exactly. ``.trace``
    never executes, so the donated buffers stay valid."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    donated, carried = engine._donated, engine._carried
    params = engine.model.params
    tables = engine._backend.device_tables()
    n_donated = leaf_count(donated)
    expected = set(range(n_donated))

    def rec(name, jitted, args, variant="") -> ProgramRecord:
        traced = jitted.trace(*args)
        return ProgramRecord(
            group=group, name=name, lowered=traced.lower(),
            donated=expected, jaxpr=traced.jaxpr, variant=variant,
        )

    # prefill_insert: borrow a backend row for the trace shapes, then put
    # the blocks straight back (paged acquire really allocates)
    row, _shared = engine._backend.acquire(0, np.zeros(1, np.int32), 2)
    engine._backend.release(0)
    kd = jax.random.key_data(jax.random.key(0))
    prompt = jnp.zeros((1, engine.prompt_bucket), jnp.int32)
    out = [
        rec("prefill_insert", engine._prefill_jit, (
            donated, carried, params, prompt, jnp.int32(1), jnp.int32(0), kd,
            jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0), jnp.int32(-1),
            jnp.int32(0), jnp.int32(2), jnp.asarray(row),
        )),
        rec("decode_step", engine._decode_jit, (donated, carried, params, tables)),
    ]
    if engine.spec is not None:
        draft = jnp.zeros((engine.slots, engine._spec_limit), jnp.int32)
        dlen = jnp.zeros((engine.slots,), jnp.int32)
        out.append(rec("verify_step", engine._verify_jit,
                       (donated, carried, params, tables, draft, dlen)))
    if engine.prefill_chunk is not None:
        # the chunked-prefill members of the prefill_insert FAMILY: one
        # fixed-(S, chunk) append-at-offset program + (paged) the host-tier
        # restore scatter. They record under the family name so the
        # ≤3-programs-per-config ceiling counts families, not members —
        # G001/G002/G003 still run per member.
        chunk_tokens = jnp.zeros((engine.slots, engine.prefill_chunk), jnp.int32)
        out.append(rec("prefill_insert", engine._chunk_jit, (
            donated, carried, params, chunk_tokens, jnp.int32(0),
            jnp.int32(engine.prefill_chunk), jnp.int32(0), kd,
            jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0), jnp.int32(-1),
            jnp.int32(0), jnp.int32(2), jnp.int32(engine.prefill_chunk + 1),
            tables,
        ), variant="chunk"))
        if engine._backend.kind.startswith("paged"):
            rows = engine._backend.blocks_per_row

            def payload_like(ref):
                if isinstance(ref, dict):
                    return {w: payload_like(v) for w, v in ref.items()}
                return jnp.zeros(
                    (rows, ref.shape[0]) + tuple(ref.shape[2:]), ref.dtype
                )

            payload = {
                "k": payload_like(donated["cache"]["k"]),
                "v": payload_like(donated["cache"]["v"]),
            }
            out.append(rec("prefill_insert", engine._restore_jit, (
                donated, payload, jnp.zeros((rows,), jnp.int32),
            ), variant="restore"))
    return out


def build_engine_programs(groups: Optional[Sequence[str]] = None) -> List[ProgramRecord]:
    from accelerate_tpu.engine import ContinuousBatchingEngine

    wanted = set(groups) if groups is not None else None
    configs = [
        ("engine.dense", {}),
        ("engine.spec", {"spec": "ngram"}),
        ("engine.paged", {"kv_cache": "paged", "block_size": 4}),
        # spec rides along so the pallas config exercises all three
        # programs (prefill + decode + verify) under the same G004 ceiling
        ("engine.paged_pallas", {"kv_cache": "paged", "block_size": 4,
                                 "attention_impl": "pallas", "spec": "ngram"}),
        # chunked prefill over a paged pool: traces the chunk + restore
        # members of the prefill_insert family alongside decode_step
        ("engine.longctx", {"kv_cache": "paged", "block_size": 4,
                            "prefill_chunk": 4}),
    ]
    model = None
    records: List[ProgramRecord] = []
    for group, kwargs in configs:
        if wanted is not None and group not in wanted:
            continue
        if model is None:
            model = _tiny_model()
        engine = ContinuousBatchingEngine(
            model, slots=2, max_len=16, readback_lag=0, **kwargs
        )
        records.extend(_engine_records(group, engine, model))
    return records


def build_train_step_program(return_state: bool = False):
    """Lower the real fused train step shape-only (abstract prepare) on a
    tiny dp=8 config — the same path benchmarks/hlo_report.py drives.

    Donation: train_step donates (params, opt_state, accum, psgd_state).
    Flat input order is params, opt_state, accum, count, scaler, psgd,
    batch; at one micro-batch an update (this config) the step carries no
    accumulator and psgd is EMPTY, so the donated flat range is the
    contiguous [0, P + O), and every index of it must alias.

    With ``return_state=True`` returns ``(record, state)`` where ``state``
    carries the abstract ``params`` and ``opt_state`` trees — graftcheck
    Level 5 (G403) walks them by path to check the master-weight/moment
    dtype contract without re-lowering.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models.llama import LlamaConfig, create_llama, llama_loss
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    for s in (AcceleratorState, GradientState, PartialState):
        s._reset_state()
    try:
        acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=8))
        model = create_llama(LlamaConfig.tiny(num_hidden_layers=2), abstract=True)
        model, opt = acc.prepare(model, optax.adamw(1e-3, mu_dtype=jnp.bfloat16))
        model.policy = None
        step = acc.train_step(llama_loss, max_grad_norm=1.0)
        batch = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        lowered = step.lower(batch)
        p = leaf_count(model.params)
        o = leaf_count(opt.opt_state)
        record = ProgramRecord(
            group="train_step", name="fused_train_step", lowered=lowered,
            donated=set(range(p + o)),
        )
        if return_state:
            return record, {"params": model.params, "opt_state": opt.opt_state}
        return record
    finally:
        for s in (AcceleratorState, GradientState, PartialState):
            s._reset_state()


def build_programs(groups: Optional[Sequence[str]] = None) -> List[ProgramRecord]:
    wanted = set(groups) if groups is not None else None
    records: List[ProgramRecord] = []
    if wanted is None or "train_step" in wanted:
        records.append(build_train_step_program())
    records.extend(build_engine_programs(groups))
    return records


# --------------------------------------------------------------------------
# per-program checks (G001-G003)
# --------------------------------------------------------------------------

def check_callbacks(rec: ProgramRecord) -> List[Finding]:
    """G001 — host round-trips inside a jitted program."""
    findings = []
    seen = set()
    if rec.jaxpr is not None:
        for prim in sorted(collect_primitives(rec.jaxpr)):
            if is_forbidden_primitive(prim):
                seen.add(prim)
    for m in _CALLBACK_CUSTOM_CALL_RE.finditer(rec.lowered.as_text()):
        seen.add(m.group(1))
    for prim in sorted(seen):
        findings.append(Finding(
            "G001", rec.source, 1,
            f"{rec.group}/{rec.name}: host callback primitive "
            f"'{prim}' inside a jitted program",
            program=f"{rec.group}/{rec.name}",
        ))
    return findings


def check_donation(rec: ProgramRecord) -> List[Finding]:
    """G002 — donated-but-unaliased and aliased-but-not-donated inputs."""
    aliased = aliased_input_indices(rec.lowered.as_text())
    findings = []
    missing = sorted(rec.donated - set(aliased))
    extra = sorted(set(aliased) - rec.donated - rec.donated_optional)
    if missing:
        findings.append(Finding(
            "G002", rec.source, 1,
            f"{rec.group}/{rec.name}: donated flat input(s) {missing} carry "
            "no tf.aliasing_output (donated-but-unused doubles peak memory)",
            program=f"{rec.group}/{rec.name}",
        ))
    if extra:
        findings.append(Finding(
            "G002", rec.source, 1,
            f"{rec.group}/{rec.name}: non-donated flat input(s) {extra} are "
            "aliased to outputs (donating the carried/ring tree breaks the "
            "deferred-readback ring)",
            program=f"{rec.group}/{rec.name}",
        ))
    return findings


def check_weak_types(rec: ProgramRecord) -> List[Finding]:
    """G003 — python-scalar (weak-typed) operands fragment the jit cache."""
    weak = weak_typed_inputs(rec.lowered)
    if not weak:
        return []
    return [Finding(
        "G003", rec.source, 1,
        f"{rec.group}/{rec.name}: weak-typed flat input(s) {sorted(weak)} "
        "(pass jnp.int32(...)/jnp.float32(...), not python scalars)",
        program=f"{rec.group}/{rec.name}",
    )]


def check_programs(records: Sequence[ProgramRecord]) -> List[Finding]:
    findings: List[Finding] = []
    for rec in records:
        findings.extend(check_callbacks(rec))
        findings.extend(check_donation(rec))
        findings.extend(check_weak_types(rec))
    return findings


# --------------------------------------------------------------------------
# baseline (G004)
# --------------------------------------------------------------------------

def collective_inventory(rec: ProgramRecord, n_devices: int = 8) -> Dict[str, int]:
    """op -> total count for the SPMD-partitioned module."""
    _compiled, hlo = compile_and_extract_spmd(rec.lowered, prefix="graftcheck_")
    collectives, _notes = parse_collectives(hlo, n_devices)
    inv: Dict[str, int] = {}
    for c in collectives:
        inv[c["op"]] = inv.get(c["op"], 0) + int(c["count"])
    return inv


def observe(records: Sequence[ProgramRecord],
            with_collectives: bool = True) -> Dict[str, Any]:
    """Summarize the built programs into the baseline-comparable shape."""
    programs: Dict[str, List[str]] = {}
    for rec in records:
        programs.setdefault(rec.group, []).append(rec.name)
    observed: Dict[str, Any] = {
        # dedup to program FAMILIES: the chunked-prefill members (chunk
        # forward, host-tier restore) record under "prefill_insert", so a
        # config's count stays prefill + decode + verify ≤ 3
        "programs": {g: sorted(set(names)) for g, names in sorted(programs.items())},
    }
    if with_collectives:
        coll: Dict[str, Dict[str, int]] = {}
        for rec in records:
            if rec.group == "train_step":
                coll[rec.name] = collective_inventory(rec)
        if coll:
            observed["collectives"] = coll
    return observed


def make_baseline(observed: Dict[str, Any]) -> Dict[str, Any]:
    baseline = dict(observed)
    baseline["ceilings"] = {
        group: ENGINE_PROGRAM_CEILING
        for group in observed.get("programs", {}) if group.startswith("engine.")
    }
    return baseline


def compare_baseline(observed: Dict[str, Any],
                     baseline: Dict[str, Any],
                     baseline_path: str = BASELINE_PATH) -> List[Finding]:
    """G004 — growth (never shrinkage) vs the committed baseline fails."""
    findings: List[Finding] = []

    def flag(msg: str, program: str = "") -> None:
        findings.append(Finding("G004", baseline_path, 1, msg, program=program))

    base_programs = baseline.get("programs", {})
    ceilings = baseline.get("ceilings", {})
    for group, names in observed.get("programs", {}).items():
        known = base_programs.get(group)
        if known is None:
            flag(f"program group '{group}' is not in the baseline "
                 "(re-baseline with --update-baseline if intended)",
                 program=group)
            continue
        for name in sorted(set(names) - set(known)):
            flag(f"unexplained new jitted program '{group}/{name}' "
                 f"(baseline knows {sorted(known)})",
                 program=f"{group}/{name}")
        ceiling = ceilings.get(
            group, ENGINE_PROGRAM_CEILING if group.startswith("engine.") else None
        )
        if ceiling is not None and len(names) > ceiling:
            flag(f"group '{group}' dispatches {len(names)} programs, over "
                 f"the {ceiling}-programs-per-config ceiling",
                 program=group)

    base_coll = baseline.get("collectives", {})
    for prog, ops in observed.get("collectives", {}).items():
        known_ops = base_coll.get(prog)
        if known_ops is None:
            if base_coll:
                flag(f"no collective baseline for program '{prog}'",
                     program=prog)
            continue
        for op, count in sorted(ops.items()):
            if count > int(known_ops.get(op, 0)):
                flag(f"collective growth in '{prog}': {op} x{count} vs "
                     f"baseline x{known_ops.get(op, 0)}",
                     program=prog)
    return findings


def load_baseline(path: str = BASELINE_PATH) -> Optional[Dict[str, Any]]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_baseline(observed: Dict[str, Any], path: str = BASELINE_PATH) -> Dict[str, Any]:
    from .lowering import atomic_write_json

    baseline = make_baseline(observed)
    atomic_write_json(baseline, path)
    return baseline


def run_program_checks(
    baseline_path: str = BASELINE_PATH,
    update_baseline: bool = False,
    groups: Optional[Sequence[str]] = None,
    with_collectives: bool = True,
    baseline_sink: Optional[list] = None,
) -> List[Finding]:
    records = build_programs(groups)
    findings = check_programs(records)
    observed = observe(records, with_collectives=with_collectives)
    if update_baseline:
        if baseline_sink is not None:
            # deferred: __main__ commits every level's baseline atomically
            # after ALL levels ran clean through — a sharding-level crash
            # must not leave a half-updated static baseline behind
            baseline_sink.append((baseline_path, make_baseline(observed)))
        else:
            write_baseline(observed, baseline_path)
        return findings
    baseline = load_baseline(baseline_path)
    if baseline is None:
        findings.append(Finding(
            "G004", baseline_path, 1,
            "baseline missing — generate it with "
            "`python -m accelerate_tpu.analysis --update-baseline`",
        ))
        return findings
    if groups is not None or not with_collectives:
        # partial runs compare only what was observed (subset semantics
        # already hold: compare_baseline iterates the observed side)
        pass
    findings.extend(compare_baseline(observed, baseline, baseline_path))
    return findings
