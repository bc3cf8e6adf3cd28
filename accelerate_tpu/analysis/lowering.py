"""Shared AOT-lowering and module-inspection helpers.

One code path for two consumers (ISSUE 8 satellite: the bench and the
checker must not fork):

* ``benchmarks/hlo_report.py`` — the compile-time perf report — imports
  :func:`parse_collectives` / :func:`ici_bytes_per_chip` /
  :func:`compile_and_extract_spmd` from here;
* ``accelerate_tpu.analysis.program`` — graftcheck Level 1 — uses the same
  helpers to extract the collective inventory for the program-budget
  baseline, plus the jaxpr/StableHLO inspection primitives below
  (:func:`collect_primitives`, :func:`aliased_input_indices`,
  :func:`weak_typed_inputs`).

Everything heavy (jax) is imported lazily inside functions so the host-lint
level of graftcheck never pays for it.
"""

from __future__ import annotations

import os
import re

# ----------------------------------------------------------- chip rooflines
# Public spec sheets; bw in bytes/s. ici_bw is the per-chip aggregate over
# all links (v5p: 3D torus, 4800 Gbps/chip), counted once per direction.
# Shared by benchmarks/hlo_report.py (the one-shot compile report) and
# graftcheck Level 6 (analysis/perf.py, the standing perf gate) — the
# ISSUE-13 dedupe satellite, same shape as the PR-9 collective-parser move.
CHIPS = {
    "v5p": dict(peak_bf16=459e12, hbm_bytes=95e9, hbm_bw=2765e9, ici_bw=600e9),
    "v5e": dict(peak_bf16=197e12, hbm_bytes=16e9, hbm_bw=819e9, ici_bw=200e9),
    "v4": dict(peak_bf16=275e12, hbm_bytes=32e9, hbm_bw=1228e9, ici_bw=300e9),
}

# Achievable fractions assumed for the roofline. None has been calibrated
# against a trace from the chip (ROADMAP D7): a large bf16 matmul reaches
# more than 0.75 of peak on a v5e, whole steps have not been measured.
MATMUL_EFF = 0.75
ICI_EFF = 0.8
HBM_EFF = 0.8

# Inter-slice data-center network: ~25 GB/s per host of sustained collective
# bandwidth — two orders of magnitude below ICI, which is why G204/G502
# treat DCN-crossing collectives as a separate, much slower lane.
DCN_BW = 25e9
DCN_EFF = 0.8


def roofline(flops: float, hbm_bytes: float, ici_bytes: float = 0.0,
             dcn_bytes: float = 0.0, chip: str = "v5p") -> dict:
    """Roofline step-time decomposition: each lane's time at its achievable
    bandwidth, the binding lane, and the predicted step time (the max —
    assumes XLA overlaps the lanes; G502 audits where that assumption is
    unearned)."""
    spec = CHIPS[chip]
    parts = {
        "compute": flops / (spec["peak_bf16"] * MATMUL_EFF),
        "hbm": hbm_bytes / (spec["hbm_bw"] * HBM_EFF),
        "ici": ici_bytes / (spec["ici_bw"] * ICI_EFF),
        "dcn": dcn_bytes / (DCN_BW * DCN_EFF),
    }
    bound = max(parts, key=lambda k: parts[k])
    return dict(
        t_compute_s=parts["compute"], t_hbm_s=parts["hbm"],
        t_ici_s=parts["ici"], t_dcn_s=parts["dcn"],
        bound=bound, step_time_s=parts[bound],
    )


def predicted_mfu(useful_flops: float, step_time_s: float,
                  chip: str = "v5p") -> float:
    """Model FLOPs utilization against the chip's bf16 peak."""
    if step_time_s <= 0.0:
        return 0.0
    return useful_flops / (step_time_s * CHIPS[chip]["peak_bf16"])


def predicted_tokens_per_s(tokens: float, step_time_s: float) -> float:
    if step_time_s <= 0.0:
        return 0.0
    return tokens / step_time_s


# ------------------------------------------------------------- HLO parsing
# "= <shape or tuple shape> all-reduce(...)"; grad reductions commonly fuse a
# whole layer's grads into ONE tuple-shaped all-reduce, so the shape part can
# contain spaces and nested brackets. "-done" halves of async pairs are
# intentionally not matched (counting them would double the -start); the
# -start form is CAPTURED so iter_collectives can report asyncness (G502).
_COLL_RE = re.compile(
    r"=\s+(?P<shape>\(?[^=]*?)\s*(?P<op>all-gather|reduce-scatter|all-reduce|"
    r"all-to-all|collective-permute)(?P<start>-start)?\(",
)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
                "f64": 8, "s8": 1, "u8": 1, "s64": 8, "u64": 8}


def _shape_bytes(shape: str) -> tuple[int, str]:
    """Sum bytes over every 'dtype[dims]' in the (possibly tuple) shape."""
    total = 0
    dtypes = []
    for m in re.finditer(r"([a-z]+[0-9]*)\[([\d,]*)\]", shape):
        dtype, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
        dtypes.append(dtype)
    if not dtypes:
        return 0, "?"
    dtype = dtypes[0] if len(set(dtypes)) == 1 else "+".join(sorted(set(dtypes)))
    return total, dtype


def _group_size(line: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:  # iota v2 form [ngroups,group_size]
        return int(m.group(2))
    return n_devices


def parse_replica_groups(line: str, n_devices: int):
    """Concrete device-id groups of one collective instruction, or None.

    Handles every form the SPMD partitioner emits: explicit
    ``replica_groups={{0,1},{2,3}}``, the iota v2 short form
    ``replica_groups=[ngroups,gsize]<=[N]`` (row-major consecutive ids),
    the transposed iota ``[ngroups,gsize]<=[d0,d1,...]T(perm)`` (ids laid
    out over the mesh then permuted — this is how cross-axis groups on a
    non-minor mesh axis print), and ``source_target_pairs`` on
    collective-permute (each pair is a 2-device group for axis-attribution
    purposes)."""
    m = re.search(r"replica_groups=\{(\{[\d, ]+\}(?:,\s*\{[\d, ]+\})*)\}", line)
    if m:
        return [
            [int(d) for d in grp.split(",")]
            for grp in re.findall(r"\{([\d, ]+)\}", m.group(1))
        ]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", line
    )
    if m:
        import numpy as np

        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        return ids.reshape(ngroups, gsize).tolist()
    m = re.search(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}", line)
    if m:
        return [
            [int(a), int(b)]
            for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))
        ]
    return None


def mesh_device_coords(mesh) -> dict:
    """device id -> per-axis coordinate tuple for a jax Mesh."""
    import numpy as np

    coords = {}
    for idx in np.ndindex(mesh.devices.shape):
        coords[mesh.devices[idx].id] = tuple(int(i) for i in idx)
    return coords


def groups_mesh_axes(groups, axis_names, coords_by_id) -> set:
    """Mesh axes that VARY inside any of a collective's device groups —
    i.e. the axes the collective actually communicates over. ``groups`` is
    the :func:`parse_replica_groups` output; unknown device ids (synthetic
    fixtures bigger than the mesh) attribute to no axis."""
    axes: set = set()
    for group in groups or ():
        known = [coords_by_id[d] for d in group if d in coords_by_id]
        if len(known) < 2:
            continue
        for pos, name in enumerate(axis_names):
            if len({c[pos] for c in known}) > 1:
                axes.add(name)
    return axes


_META_SRC_RE = re.compile(r'source_file="([^"]+)"(?:.*?source_line=(\d+))?')
_META_OP_RE = re.compile(r'op_name="([^"]+)"')


def split_computations(hlo: str):
    """(comps, entry): computation name -> instruction lines, + entry name.

    Computation definitions start at column 0; instructions are indented.
    Older XLA text prints "%name (params) -> ... {", newer emitters drop
    the parameter list (and the % sigils) and print just "name {" — accept
    both by matching only the leading name up to a paren OR the brace."""
    comps: dict[str, list[str]] = {}
    entry = None
    name = None
    for raw in hlo.splitlines():
        header = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*[({]", raw)
        if header and raw.rstrip().endswith("{"):
            name = header.group(2)
            comps[name] = []
            if header.group(1):
                entry = name
        elif name is not None:
            comps[name].append(raw)
    if entry is None:  # single-computation module
        entry = next(iter(comps), None)
    return comps, entry


def iter_collectives(hlo: str, n_devices: int):
    """Per-INSTRUCTION collective records with while-loop trip weighting.

    Returns ``(instrs, notes)``. Each record carries everything the
    aggregate inventory (:func:`parse_collectives`) and the sharding
    auditor (graftcheck Level 3) need: ``op`` (with the rs-pattern
    rewrite applied), ``dtype``, ``bytes``, ``group`` (devices per group),
    ``groups`` (concrete id groups, or None when unparseable),
    ``multiplier`` (product of enclosing while trip counts), ``comp``,
    ``result``/``operand`` instruction names, ``async`` (True when lowered
    as the ``-start`` half of an async pair — the overlap evidence G502
    audits), and the jax ``op_name`` / ``source`` metadata when present."""
    comps, entry = split_computations(hlo)

    def trip_count(line: str, cond_name):
        # Post-optimization modules stamp the statically-known trip count on
        # the while op itself
        m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line)
        if m:
            return int(m.group(1))
        # Post-SPMD modules don't: read the condition's compare-against-
        # constant bound (induction always starts at 0 with step 1 for
        # lax.scan lowerings)
        body = comps.get(cond_name or "", [])
        consts = {}
        for cline in body:
            cm = re.match(
                r"\s*%?([\w.\-]+)\s*=\s*s32\[\]\s*constant\((\d+)\)", cline
            )
            if cm:
                consts[cm.group(1)] = int(cm.group(2))
        for cline in body:
            cm = re.search(r"compare\(%?([\w.\-]+),\s*%?([\w.\-]+)\)", cline)
            if cm:
                for operand in (cm.group(1), cm.group(2)):
                    if operand in consts:
                        return consts[operand]
        if len(consts) == 1:
            return next(iter(consts.values()))
        return None

    notes = []
    instrs: list[dict] = []

    def reduce_scatter_like(comp: str, result_name: str) -> bool:
        """An all-reduce whose every consumer is a (dynamic-)slice IS a
        reduce-scatter the backend decomposed (XLA:CPU) or the
        ReduceScatterCreator pass will re-fuse (TPU pipeline) — count it at
        reduce-scatter cost."""
        uses = [
            l for l in comps.get(comp, [])
            if result_name + ")" in l or result_name + "," in l
            or l.rstrip().endswith(result_name)
        ]
        uses = [l for l in uses if f"= " in l and result_name not in l.split("=")[0]]
        return bool(uses) and all(
            re.search(r"dynamic-slice|slice\(", l) for l in uses
        )

    def walk(comp: str, multiplier: int, seen: tuple):
        if comp in seen or comp not in comps:
            return
        for line in comps[comp]:
            wm = re.search(r"while\(", line)
            if wm:
                targets = dict(
                    re.findall(r"(body|condition)=%?([\w.\-]+)", line)
                )
                body = targets.get("body")
                cond = targets.get("condition")
                tc = trip_count(line, cond)
                if tc is None:
                    tc = 1
                    notes.append(
                        f"while body {body!r}: trip count unparseable, counted once"
                    )
                if body:
                    walk(body, multiplier * tc, seen + (comp,))
                continue
            # tuple shapes embed /*index=N*/ comments whose '=' breaks the
            # shape capture — strip comments before matching
            cm = _COLL_RE.search(re.sub(r"/\*.*?\*/", "", line))
            if cm:
                nbytes, dtype = _shape_bytes(cm.group("shape"))
                g = _group_size(line, n_devices)
                op = cm.group("op")
                nm = re.match(r"\s*(%?[\w.\-]+)\s*=", line)
                result = nm.group(1).lstrip("%") if nm else "?"
                if op == "all-reduce" and nm and reduce_scatter_like(comp, result):
                    op = "all-reduce[rs-pattern]"
                om = re.search(
                    r"(?:all-gather|reduce-scatter|all-reduce|all-to-all|"
                    r"collective-permute)(?:-start)?\(\s*%?([\w.\-]+)", line
                )
                sm = _META_SRC_RE.search(line)
                opm = _META_OP_RE.search(line)
                instrs.append({**dict(
                    op=op, dtype=dtype, bytes=nbytes, group=g,
                    groups=parse_replica_groups(line, n_devices),
                    multiplier=multiplier, comp=comp, result=result,
                    operand=om.group(1) if om else "?",
                    op_name=opm.group(1) if opm else "",
                    source=(f"{os.path.basename(sm.group(1))}:{sm.group(2)}"
                            if sm and sm.group(2)
                            else os.path.basename(sm.group(1)) if sm else ""),
                ), "async": bool(cm.group("start"))})
            # calls/fusions that might contain collectives (conditionals)
            for sub in re.findall(r"(?:true_computation|false_computation|"
                                  r"branch_computations)=\{?%?([\w.\-]+)", line):
                walk(sub, multiplier, seen + (comp,))
            cm2 = re.search(r"\bcall\(.*to_apply=%?([\w.\-]+)", line)
            if cm2:
                walk(cm2.group(1), multiplier, seen + (comp,))
    walk(entry, 1, ())
    return instrs, notes


def parse_collectives(hlo: str, n_devices: int):
    """Aggregate collective inventory with while-loop trip counts.

    Walks the entry computation (via :func:`iter_collectives`) and sums
    per-instruction records into one row per distinct (op, dtype, bytes),
    multiplying ops inside while bodies by the loop trip count (parsed from
    the condition's compare-against-constant; layer scans and grad-accum
    loops all lower this way). Unparseable trip counts fall back to 1 with
    a note — counts are then LOWER bounds."""
    instrs, notes = iter_collectives(hlo, n_devices)
    totals: dict[tuple, dict] = {}
    for rec in instrs:
        key = (rec["op"], rec["dtype"], rec["bytes"])
        agg = totals.setdefault(
            key, dict(op=rec["op"], dtype=rec["dtype"], bytes=rec["bytes"],
                      group=rec["group"], count=0),
        )
        agg["count"] += rec["multiplier"]
    return list(totals.values()), notes


def ici_bytes_per_chip(collectives) -> float:
    """Ring-algorithm bytes each chip must move over ICI per step."""
    total = 0.0
    for rec in collectives:
        g = rec["group"]
        if g <= 1:
            continue
        frac = (g - 1) / g
        if rec["op"] in ("all-gather", "reduce-scatter",
                         "all-reduce[rs-pattern]"):
            total += rec["bytes"] * frac * rec["count"]
        elif rec["op"] == "all-reduce":
            total += 2 * rec["bytes"] * frac * rec["count"]
        elif rec["op"] == "collective-permute":
            total += rec["bytes"] * rec["count"]
    return total


def compile_and_extract_spmd(lowered, prefix="hlo_report_", want_dump=True):
    """Compile with the SPMD-pass dump and return (compiled, hlo_text) —
    the post-partitioning module when the dump is available, else the
    final optimized text (CPU-legalized; dtype/RS info degraded). Shared by
    the train and decode reports so dump/selection fixes apply once."""
    import glob as _glob
    import tempfile

    if not want_dump:
        return lowered.compile(), None
    dump_dir = tempfile.mkdtemp(prefix=prefix)
    compiled = lowered.compile(
        {"xla_dump_to": dump_dir, "xla_dump_hlo_pass_re": "spmd.*"}
    )
    spmd = sorted(
        _glob.glob(os.path.join(dump_dir, "*after_spmd-partitioning*"))
    )
    if spmd:
        with open(spmd[-1]) as f:
            return compiled, f.read()
    return compiled, None


# per-device HBM accounting fields XLA's memory_analysis exposes; one table
# shared by benchmarks/hlo_report.py and graftcheck G203 so the bench report
# and the static budget gate can never disagree on what "live" means.
_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "generated_code_size_in_bytes")


def memory_table(compiled) -> dict:
    """Static per-device HBM accounting of a compiled program.

    Returns the raw ``memory_analysis()`` byte fields plus ``hbm_live`` —
    arguments + temps, since donated outputs alias their argument buffers
    (the same estimate ``benchmarks/hlo_report.py`` reports as
    ``hbm_live_estimate``). Fields XLA does not expose on this backend are
    simply absent."""
    mem = compiled.memory_analysis()
    table = {
        k: int(getattr(mem, k)) for k in _MEM_FIELDS if hasattr(mem, k)
    }
    table["hbm_live"] = (
        table.get("argument_size_in_bytes", 0)
        + table.get("temp_size_in_bytes", 0)
    )
    return table


def atomic_write_json(obj, path: str) -> None:
    """Write-to-temp + rename so a crash mid-update never leaves a torn
    baseline; both graftcheck baselines commit through this."""
    import json
    import tempfile

    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ------------------------------------------------- graftcheck inspection
# Primitives that smuggle host work or host<->device transfers into a jitted
# program. Matching is by exact name OR the "callback" substring so jax
# renames (debug_callback / pure_callback / io_callback / ordered variants)
# stay covered.
_FORBIDDEN_EXACT = frozenset({"infeed", "outfeed", "host_local_array_to_global",
                              "global_array_to_host_local"})


def is_forbidden_primitive(name: str) -> bool:
    return "callback" in name or name in _FORBIDDEN_EXACT


def collect_primitives(closed_jaxpr) -> set:
    """Every primitive name reachable from a (Closed)Jaxpr, recursing into
    sub-jaxprs carried in eqn params (pjit, scan, while, cond bodies)."""
    from jax._src import core as jcore

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc: set = set()

    def visit(jx):
        for eqn in jx.eqns:
            acc.add(eqn.primitive.name)
            for val in eqn.params.values():
                for sub in _subjaxprs(val, jcore):
                    visit(sub)

    visit(jaxpr)
    return acc


def _subjaxprs(val, jcore):
    if isinstance(val, jcore.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jcore.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _subjaxprs(item, jcore)


# MLIR signature args print as "%argN: tensor<...> {attrs}" (no space before
# the colon); body uses print with a spaced " : " trailing type, so this
# pattern only matches the @main signature's parameters.
_ARG_RE = re.compile(r"%arg(\d+): tensor<[^>]*>(?:\s*\{([^}]*)\})?")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DONOR_RE = re.compile(r"jax\.buffer_donor\s*=\s*true")


def aliased_input_indices(stablehlo_text: str) -> dict:
    """Map flat input index -> aliased output index, parsed from the arg
    attributes jax stamps on donated inputs at lowering time
    (platform-independent: present even on the CPU backend, which later
    drops donation at runtime). Unsharded programs carry the explicit
    pairing ``tf.aliasing_output = N``; sharded programs defer the pairing
    to XLA and mark the input ``jax.buffer_donor = true`` instead — those
    map to output index -1 (donated, pairing decided at compile time)."""
    aliased = {}
    for m in _ARG_RE.finditer(stablehlo_text):
        attrs = m.group(2) or ""
        am = _ALIAS_RE.search(attrs)
        if am:
            aliased[int(m.group(1))] = int(am.group(1))
        elif _DONOR_RE.search(attrs):
            aliased[int(m.group(1))] = -1
    return aliased


def input_count(stablehlo_text: str) -> int:
    """Number of flat inputs of the lowered module's @main."""
    idx = [int(m.group(1)) for m in _ARG_RE.finditer(stablehlo_text)]
    return max(idx) + 1 if idx else 0


def flat_in_avals(lowered):
    """Flattened input avals of a Lowered/Traced, in @main argument order."""
    import jax

    return jax.tree_util.tree_leaves(lowered.in_avals)


def weak_typed_inputs(lowered) -> list:
    """Flat input indices whose aval is weak-typed — python-scalar operands
    that fragment the jit cache (a later call with a strongly-typed array of
    the same shape/dtype compiles a SECOND program)."""
    return [
        i for i, av in enumerate(flat_in_avals(lowered))
        if getattr(av, "weak_type", False)
    ]


def abstractify(tree):
    """ShapeDtypeStruct skeleton of a pytree (nothing materialized)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def leaf_count(tree) -> int:
    import jax

    return len(jax.tree_util.tree_leaves(tree))


# --------------------------------------------- numerics (graftcheck Level 5)
# StableHLO text parsers shared by analysis/numerics.py. All of these work on
# ``lowered.as_text()`` (pre-optimization StableHLO), where dtypes are still
# the ones jax traced — the CPU backend's later f64→f32 legalization etc.
# never degrades them.

def count_primitives(closed_jaxpr) -> dict:
    """Primitive name -> equation count over a (Closed)Jaxpr, recursing into
    sub-jaxprs. Unlike :func:`collect_primitives` (a set) this counts call
    SITES — the G404 jaxpr check needs to distinguish one sampler from two."""
    from jax._src import core as jcore

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc: dict = {}

    def visit(jx):
        for eqn in jx.eqns:
            acc[eqn.primitive.name] = acc.get(eqn.primitive.name, 0) + 1
            for val in eqn.params.values():
                for sub in _subjaxprs(val, jcore):
                    visit(sub)

    visit(jaxpr)
    return acc


def flat_out_avals(lowered):
    """Flattened OUTPUT avals of a Lowered/Traced, in @main result order:
    the per-output ShapeDtypeStructs of its ``out_info``."""
    import jax

    return jax.tree_util.tree_leaves(lowered.out_info)


# 'tensor<2x8x64xbf16>' -> 'bf16'; 'tensor<f32>' (rank 0) -> 'f32';
# 'tensor<4x?xi8>' (dynamic dim) -> 'i8'
def tensor_elem_type(tensor: str) -> str:
    m = re.search(r"tensor<(?:[\d?]+x)*([^x>]+)>", tensor)
    return m.group(1) if m else "?"


_F64_RE = re.compile(r"tensor<(?:[\d?]+x)*f64>")


def f64_lines(stablehlo_text: str):
    """(1-based line number, stripped line) of every op touching an f64
    tensor — any hit in a hot program is a G401 unintended promotion."""
    hits = []
    for i, line in enumerate(stablehlo_text.splitlines(), 1):
        if _F64_RE.search(line):
            hits.append((i, line.strip()))
    return hits


# 'stablehlo.dot_general ... : (tensor<AxBxbf16>, tensor<BxCxbf16>) ->
# tensor<AxCxbf16>' / same for convolution. The trailing function-type
# signature carries both operand and result element types.
_DOT_RE = re.compile(
    r"stablehlo\.(dot_general|convolution)\b.*?:\s*"
    r"\((tensor<[^>]+>),\s*(tensor<[^>]+>)\)\s*->\s*(tensor<[^>]+>)"
)

# Dtypes whose dot_general MUST accumulate wider (f32) per the numerics
# contract; f32/f64 dots accumulate natively.
_NARROW = frozenset({"bf16", "f16", "i8", "si8", "ui8",
                     "f8E4M3FN", "f8E5M2", "f8E4M3FNUZ", "f8E5M2FNUZ"})


def narrow_dot_ops(stablehlo_text: str):
    """Every dot_general/convolution with narrow (bf16/f16/int8/fp8)
    operands: dicts of ``line`` (1-based), ``op``, ``lhs``/``rhs``/``out``
    element types, and ``accumulates`` — True when the result element type
    is wider than the operands (i.e. ``preferred_element_type`` widened the
    accumulator, the G402 contract)."""
    out = []
    for i, line in enumerate(stablehlo_text.splitlines(), 1):
        m = _DOT_RE.search(line)
        if not m:
            continue
        lhs = tensor_elem_type(m.group(2))
        rhs = tensor_elem_type(m.group(3))
        res = tensor_elem_type(m.group(4))
        if lhs in _NARROW or rhs in _NARROW:
            out.append(dict(line=i, op=m.group(1), lhs=lhs, rhs=rhs, out=res,
                            accumulates=res not in _NARROW))
    return out


# Compact reduce print form:
#   %1 = stablehlo.reduce(%0 init: %cst) applies stablehlo.add across
#        dimensions = [0] : (tensor<2x3xbf16>, tensor<bf16>) -> tensor<3xbf16>
_REDUCE_RE = re.compile(
    r"stablehlo\.reduce\(.*?\)\s+applies\s+stablehlo\.add\s+across\s+"
    r"dimensions\s*=\s*\[([\d, ]*)\]\s*:\s*\(tensor<([^>]+)>,"
)


def narrow_add_reduces(stablehlo_text: str):
    """Add-reductions whose operand element type is bf16/f16 — sums
    accumulated in half precision (``jnp.sum`` upcasts internally, so these
    only appear via raw ``lax.reduce``, explicitly narrow reductions, or
    einsum decompositions). ``elements`` is the reduced-element count
    (product of the reduced dims) so callers can separate long drift-prone
    accumulations from short per-head partial sums."""
    out = []
    for i, line in enumerate(stablehlo_text.splitlines(), 1):
        m = _REDUCE_RE.search(line)
        if not m:
            continue
        elem = tensor_elem_type(f"tensor<{m.group(2)}>")
        if elem not in ("bf16", "f16"):
            continue
        dims = [int(d) for d in m.group(1).replace(" ", "").split(",") if d]
        shape = [int(s) for s in m.group(2).split("x")[:-1] if s.isdigit()]
        n = 1
        for d in dims:
            if d < len(shape):
                n *= shape[d]
        out.append(dict(line=i, elem=elem, elements=n))
    return out


# scatter lowers in the quoted generic form with the combiner as a region:
#   "stablehlo.scatter"(%a, %i, %u) <{...}> ({
#     ^bb0(%arg0: tensor<f32>, %arg1: tensor<f32>):
#       %x = stablehlo.add %arg0, %arg1 : tensor<f32>
#       stablehlo.return %x : tensor<f32>
#   }) : ...
_SCATTER_RE = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\)', re.DOTALL)


def unordered_reduction_inventory(stablehlo_text: str) -> dict:
    """op -> count of lowered ops with unordered-reduction semantics (the
    G405 inventory): scatter-add combiners, select_and_scatter, and the
    cross-replica reduces whose contribution order the runtime does not fix.
    Plain elementwise/reduce ops are deterministic on TPU and not counted."""
    inv: dict = {}

    def bump(op, n=1):
        if n:
            inv[op] = inv.get(op, 0) + n

    for m in _SCATTER_RE.finditer(stablehlo_text):
        body = m.group(0)
        if "stablehlo.add" in body:
            bump("scatter-add")
    bump("select_and_scatter", stablehlo_text.count("select_and_scatter"))
    bump("reduce_scatter", len(re.findall(
        r"stablehlo\.reduce_scatter\b", stablehlo_text)))
    bump("all_reduce", len(re.findall(
        r"stablehlo\.all_reduce\b", stablehlo_text)))
    return inv
