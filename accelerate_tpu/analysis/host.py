"""graftcheck Level 2: AST lint over the host-side code (rules G101–G107).

Pure-stdlib (ast + re) — no jax import, so ``--level host`` runs in well
under a second. Rules are repo-specific by design; each one encodes an
invariant a past PR or review cycle established:

* G101 — engine/serving hot loops must not block on device values
  (PR 2/PR 4 pipelining). Deliberate sync points carry ``# graft: sync-ok``.
* G102 — every coordination wait needs a timeout route, and every
  ``wait_for_everyone`` barrier a site tag, so a dead peer produces a
  nameable ``BarrierTimeoutError`` instead of a silent hang (PR 1/PR 5).
* G103 — raise the ``utils/fault.py`` taxonomy, not bare RuntimeError, in
  modules that have one (clients dispatch on ``retriable``; PR 1/PR 3).
* G104 — no tracker/metrics I/O while holding the server lock (the PR 4
  review's lock-held-flush stall).
* G105 — a fault-injection point referenced by tests/docs must exist in
  code, or the test silently stops testing anything (PR 1 harness).
* G107 — tracing discipline (PR 11 flight recorder): no host clocks or
  tracer calls inside jitted functions (they run once at trace time and
  bake a constant — or worse, retrace), and ``tracing.span`` only as
  ``with`` context managers (a span that is never ``__exit__``-ed
  never lands in the ring, so it silently records nothing).
* G108 — metric-name discipline (PR 15 observatory): every
  ``bump``/``gauge``/``observe`` call site names its metric with a
  literal (or literal-fragment f-string) matching ``[a-z0-9_/]+`` —
  Prometheus-mappable, grep-able, and impossible to typo into a fresh
  ad-hoc namespace nobody scrapes. Forwarding wrappers named
  ``bump``/``gauge``/``observe`` themselves (the registered-prefix
  dialects ``ServingMetrics``/``FleetMetrics``) are the one sanctioned
  pass-through.

Waivers are line-scoped comments on the finding line or the line above:
the per-rule token (``sync-ok``, ``wait-ok``, ``raise-ok``, ``lock-ok``,
``fault-ok``, ``trace-ok``, ``metric-ok``) or the universal ``gXXX-ok``
form, e.g. ``# graft: g101-ok``.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, List, Optional, Set

from . import Finding

# ------------------------------------------------------------ rule scoping
# Modules whose loops sit on the decode/serving critical path: one stray
# blocking readback stalls the whole pipelining scheme.
HOT_MODULES = {"engine.py", "serving.py"}
# Modules where the fault taxonomy applies (they import/raise it already).
TYPED_RAISE_MODULES = {
    "engine.py", "serving.py", "kvcache.py", "telemetry.py", "elastic.py",
    "checkpointing.py", "fleet.py", "controller.py", "kvtransfer.py",
}

# Device-value taint seeds: engine/serving state that holds jax Arrays.
_SEED_ATTRS = {"_donated", "_carried", "_ring"}
# Calls whose results are device values (jitted dispatches, generate).
_DEVICE_CALL_RE = re.compile(r"(_jit|_generate_fn)$")
# Lock attributes guarding the serving dispatch/admission path.
_LOCK_ATTR_RE = re.compile(r"^(_lock|_wake|_mu)\w*$|^lock$")
# Tracker/metrics I/O entry points that must never run under those locks.
_TRACKER_SINKS = {"_flush_metrics", "maybe_flush", "log_registry", "log_batch"}

_WAIVER_RE = re.compile(r"#\s*graft:\s*([\w ,-]+)")
_RULE_TOKENS = {
    "G101": "sync-ok",
    "G102": "wait-ok",
    "G103": "raise-ok",
    "G104": "lock-ok",
    "G105": "fault-ok",
    "G107": "trace-ok",
    "G108": "metric-ok",
    # Level 5's AST half (analysis/numerics.py) shares this waiver table
    "G404": "key-ok",
}

FAULT_ENV = "ACCELERATE_TPU_FAULT_INJECT"


# --------------------------------------------------------------- waivers
def parse_waivers(text: str) -> dict:
    """line number -> set of waiver tokens on that line."""
    out: dict = {}
    for i, line in enumerate(text.splitlines(), start=1):
        m = _WAIVER_RE.search(line)
        if m:
            out[i] = {tok.strip().lower() for tok in m.group(1).split(",")}
    return out


def _waived(code: str, line: int, waivers: dict) -> bool:
    allowed = {_RULE_TOKENS[code], f"{code.lower()}-ok"}
    for ln in (line, line - 1):
        if waivers.get(ln, set()) & allowed:
            return True
    return False


# ---------------------------------------------------------- ast utilities
def _attr_chain(node: ast.AST) -> List[str]:
    """x.y.z -> ["x", "y", "z"]; non-name roots contribute nothing."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


def _is_np_call(func: ast.AST, name: str) -> bool:
    return (
        isinstance(func, ast.Attribute)
        and func.attr == name
        and isinstance(func.value, ast.Name)
        and func.value.id in ("np", "numpy", "onp")
    )


def _is_jax_device_get(func: ast.AST) -> bool:
    return isinstance(func, ast.Attribute) and func.attr == "device_get"


def _assigned_names(target: ast.AST) -> Iterable[str]:
    for node in ast.walk(target):
        if isinstance(node, ast.Name):
            yield node.id


# ------------------------------------------------------------------- G101
class _TaintLint:
    """Per-function forward taint pass: names assigned from device-valued
    expressions (jit dispatch results, the arena/ring state) are tainted;
    a materializing call (np.asarray / device_get) both *fires the rule*
    and launders its result back to host data, so downstream host math on
    the materialized copy stays quiet."""

    def __init__(self, relpath: str, waivers: dict, findings: list):
        self.relpath = relpath
        self.waivers = waivers
        self.findings = findings
        self.tainted: Set[str] = set()

    # -- taint classification
    def _expr_taints(self, node: Optional[ast.AST]) -> bool:
        """Does evaluating this expression yield (or contain) device data?"""
        if node is None:
            return False
        for sub in ast.walk(node):
            if self._direct_seed(sub):
                return True
            if isinstance(sub, ast.Name) and sub.id in self.tainted:
                return True
        return False

    def _direct_seed(self, sub: ast.AST) -> bool:
        if isinstance(sub, ast.Attribute) and sub.attr in _SEED_ATTRS:
            return True
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
            if _DEVICE_CALL_RE.search(sub.func.attr):
                return True
        return False

    def _is_materializer(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        return (
            _is_np_call(node.func, "asarray")
            or _is_np_call(node.func, "array")
            or _is_jax_device_get(node.func)
        )

    # -- sinks
    def _check_call(self, node: ast.Call) -> None:
        func = node.func
        line = node.lineno
        args_taint = any(self._expr_taints(a) for a in node.args)
        direct = any(
            any(self._direct_seed(s) for s in ast.walk(a)) for a in node.args
        )
        if isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
            self._emit(line, "block_until_ready() stalls the dispatch pipeline")
        elif _is_jax_device_get(func):
            self._emit(line, "jax.device_get() is a blocking device readback")
        elif (_is_np_call(func, "asarray") or _is_np_call(func, "array")) and args_taint:
            self._emit(line, "np.asarray on a device value blocks until the "
                             "program completes")
        elif isinstance(func, ast.Attribute) and func.attr == "item" and (
            self._expr_taints(func.value)
        ):
            self._emit(line, ".item() on a device value is a blocking readback")
        elif isinstance(func, ast.Name) and func.id in ("float", "int", "bool") and direct:
            self._emit(line, f"{func.id}() on a device value is a blocking readback")

    def _emit(self, line: int, msg: str) -> None:
        if not _waived("G101", line, self.waivers):
            self.findings.append(Finding("G101", self.relpath, line, msg))

    # -- forward walk
    def run(self, fn: ast.AST) -> None:
        for stmt in getattr(fn, "body", []):
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                self._check_call(node)
        # propagate AFTER checking, in statement order
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = getattr(stmt, "value", None)
            targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            taint = self._expr_taints(value) and not self._is_materializer(value)
            for tgt in targets:
                for name in _assigned_names(tgt):
                    if taint:
                        self.tainted.add(name)
                    else:
                        self.tainted.discard(name)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            if self._expr_taints(stmt.iter):
                self.tainted.update(_assigned_names(stmt.target))
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                if item.optional_vars is not None and self._expr_taints(item.context_expr):
                    self.tainted.update(_assigned_names(item.optional_vars))
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.stmt):
                self._stmt(child)


# --------------------------------------------------------------- the lint
def lint_source(text: str, relpath: str) -> List[Finding]:
    """Lint one python source (rules G101–G104). ``relpath`` decides which
    module-scoped rules apply; G105 is cross-file and lives in
    :func:`check_fault_registry`."""
    try:
        tree = ast.parse(text)
    except SyntaxError as exc:
        return [Finding("G000", relpath, exc.lineno or 0,
                        f"unparseable: {exc.msg}")]
    waivers = parse_waivers(text)
    base = os.path.basename(relpath)
    findings: List[Finding] = []

    # G101 — per-function taint pass, hot modules only
    if base in HOT_MODULES:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _TaintLint(relpath, waivers, findings).run(node)

    # G102 — unbounded waits + anonymous barriers, package-wide
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        line = node.lineno
        func = node.func
        bare = not node.args and not node.keywords
        if isinstance(func, ast.Attribute) and func.attr in ("wait", "join") and bare:
            # ".".join(...) always has args, so a bare join is a thread/queue
            # join; a bare wait is a Condition/Event/process wait
            if not _waived("G102", line, waivers):
                findings.append(Finding(
                    "G102", relpath, line,
                    f"bare .{func.attr}() can block forever — pass a timeout "
                    "or waive with '# graft: wait-ok'",
                ))
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None
        )
        if name == "wait_for_everyone" and bare:
            if not _waived("G102", line, waivers):
                findings.append(Finding(
                    "G102", relpath, line,
                    "anonymous barrier: pass a site tag so a stuck peer "
                    "raises a nameable BarrierTimeoutError",
                ))

    # G103 — untyped raises where the taxonomy applies
    if base in TYPED_RAISE_MODULES:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            exc_name = None
            if isinstance(exc, ast.Call) and isinstance(exc.func, ast.Name):
                exc_name = exc.func.id
            elif isinstance(exc, ast.Name):
                exc_name = exc.id
            if exc_name in ("RuntimeError", "Exception"):
                if not _waived("G103", node.lineno, waivers):
                    findings.append(Finding(
                        "G103", relpath, node.lineno,
                        f"bare {exc_name}: use (or add) a utils/fault.py "
                        "taxonomy type so callers can dispatch on it",
                    ))

    # G104 — tracker I/O under the server lock
    _lint_lock_held(tree, relpath, waivers, findings)

    # G107 — tracing discipline (tracing.py implements the machinery and is
    # exempt from the span-usage half; the jit half applies everywhere)
    _lint_jitted_tracing(tree, relpath, waivers, findings)
    if base != "tracing.py":
        _lint_span_discipline(tree, relpath, waivers, findings)

    # G108 — metric-name discipline, package-wide
    _lint_metric_names(tree, relpath, waivers, findings)

    return _dedupe(findings)


# G108 — metric-name discipline. The registry maps names straight into
# the exporter's Prometheus families; a name outside [a-z0-9_/]+ (or a
# computed one) is a metric that silently lands in a namespace nobody
# scrapes or greps for.
_METRIC_METHODS = {"bump", "gauge", "observe"}
_METRIC_NAME_RE = re.compile(r"^[a-z0-9_/]+$")
_METRIC_FRAG_RE = re.compile(r"^[a-z0-9_/]*$")


def _lint_metric_names(tree, relpath, waivers, findings) -> None:
    # Forwarding wrappers named bump/gauge/observe (ServingMetrics,
    # FleetMetrics, MetricsRegistry itself) ARE the registered-prefix
    # path: their own call sites are checked, the variable they forward
    # is not re-flagged.
    wrapper_spans = [
        (node.lineno, node.end_lineno or node.lineno)
        for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _METRIC_METHODS
    ]

    def in_wrapper(line: int) -> bool:
        return any(lo <= line <= hi for lo, hi in wrapper_spans)

    # `for name in ("a", "b"): registry.gauge(name, 0.0)` — the names ARE
    # literals, hoisted into a loop; accept the loop variable inside the
    # loop body and validate the tuple's elements instead (only for loops
    # a metric call actually consumes).
    literal_loops = []  # (var, lo, hi, elts)
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.For, ast.AsyncFor))
                and isinstance(node.target, ast.Name)
                and isinstance(node.iter, (ast.Tuple, ast.List, ast.Set))):
            continue
        elts = node.iter.elts
        if elts and all(
            isinstance(e, ast.Constant) and isinstance(e.value, str)
            for e in elts
        ):
            literal_loops.append((
                node.target.id, node.lineno,
                node.end_lineno or node.lineno, elts,
            ))

    def literal_loop_check(name_arg: ast.AST, line: int) -> bool:
        """True when ``name_arg`` is a literal-tuple loop variable; the
        elements themselves are validated (and flagged) here."""
        if not isinstance(name_arg, ast.Name):
            return False
        for var, lo, hi, elts in literal_loops:
            if name_arg.id != var or not lo <= line <= hi:
                continue
            for e in elts:
                if (not _METRIC_NAME_RE.match(e.value)
                        and not _waived("G108", e.lineno, waivers)):
                    findings.append(Finding(
                        "G108", relpath, e.lineno,
                        f"metric name {e.value!r} must match [a-z0-9_/]+ "
                        "(Prometheus-mappable; '# graft: metric-ok' waives)",
                    ))
            return True
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _METRIC_METHODS):
            continue
        if node.args:
            name_arg = node.args[0]
        else:
            name_arg = next(
                (kw.value for kw in node.keywords if kw.arg == "name"), None
            )
        if name_arg is None:
            continue
        line = node.lineno
        if _waived("G108", line, waivers):
            continue
        if isinstance(name_arg, ast.Constant) and isinstance(name_arg.value, str):
            if not _METRIC_NAME_RE.match(name_arg.value):
                findings.append(Finding(
                    "G108", relpath, line,
                    f"metric name {name_arg.value!r} must match "
                    "[a-z0-9_/]+ (Prometheus-mappable; '# graft: "
                    "metric-ok' waives)",
                ))
        elif isinstance(name_arg, ast.JoinedStr):
            for part in name_arg.values:
                if (isinstance(part, ast.Constant)
                        and isinstance(part.value, str)
                        and not _METRIC_FRAG_RE.match(part.value)):
                    findings.append(Finding(
                        "G108", relpath, line,
                        f"metric name fragment {part.value!r} must match "
                        "[a-z0-9_/]* (Prometheus-mappable; '# graft: "
                        "metric-ok' waives)",
                    ))
                    break
        elif not in_wrapper(line) and not literal_loop_check(name_arg, line):
            findings.append(Finding(
                "G108", relpath, line,
                f".{func.attr}() metric name is not a literal — computed "
                "names fork ad-hoc namespaces; use a literal/f-string or "
                "a registered-prefix wrapper ('# graft: metric-ok' waives)",
            ))


def _lint_lock_held(tree, relpath, waivers, findings) -> None:
    def visit(node: ast.AST, held: bool) -> None:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                ctx = item.context_expr
                if isinstance(ctx, ast.Attribute) and _LOCK_ATTR_RE.match(ctx.attr):
                    held = True
        if held and isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            sink = (chain and chain[-1] in _TRACKER_SINKS) or any(
                part in ("tracker", "trackers") for part in chain[:-1]
            )
            if sink and not _waived("G104", node.lineno, waivers):
                findings.append(Finding(
                    "G104", relpath, node.lineno,
                    f"{'.'.join(chain)}() performs tracker/metrics I/O while "
                    "holding the server lock (stalls every submitter)",
                ))
        for child in ast.iter_child_nodes(node):
            # a nested function body does not inherit the caller's lock
            child_held = held and not isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)
            )
            visit(child, child_held)

    visit(tree, False)


# ------------------------------------------------------------------- G107
# Host clocks: called at trace time they bake a constant into the program
# (and a tracer ring append inside traced code is pure overhead/retrace bait).
_CLOCK_FUNCS = {"time", "monotonic", "perf_counter", "perf_counter_ns", "monotonic_ns"}
_SPAN_FUNCS = {"span"}
_TRACER_FUNCS = _SPAN_FUNCS | {"flight_dump", "new_trace_id", "get_tracer"}


def _jit_wrapped_names(tree: ast.AST) -> Set[str]:
    """Function names passed positionally to a ``*jit*(...)`` call, e.g.
    ``self._decode_jit = jax.jit(_decode_impl, ...)``."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not chain or "jit" not in chain[-1]:
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif isinstance(arg, ast.Attribute):
                names.add(arg.attr)
    return names


def _is_jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if any("jit" in part for part in _attr_chain(target)):
            return True
    return False


def _lint_jitted_tracing(tree, relpath, waivers, findings) -> None:
    """G107 (jit half): no host clocks or tracer calls inside code jax will
    trace. A function counts as jitted when it is decorated with ``*jit*``,
    passed to a ``*jit*(...)`` call, or follows the repo's ``*_impl`` naming
    convention for staged-out program bodies."""
    jit_names = _jit_wrapped_names(tree)
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not (
            fn.name.endswith("_impl")
            or fn.name in jit_names
            or _is_jit_decorated(fn)
        ):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain:
                continue
            leaf = chain[-1]
            offense = None
            if len(chain) >= 2 and chain[0] == "time" and leaf in _CLOCK_FUNCS:
                offense = f"host clock {'.'.join(chain)}()"
            elif "tracing" in chain[:-1] or leaf in _TRACER_FUNCS:
                offense = f"tracer call {'.'.join(chain)}()"
            if offense and not _waived("G107", node.lineno, waivers):
                findings.append(Finding(
                    "G107", relpath, node.lineno,
                    f"{offense} inside jitted function {fn.name!r}: runs once "
                    "at trace time (baked constant / retrace hazard) — hoist "
                    "to the host wrapper or waive with '# graft: trace-ok'",
                ))


def _lint_span_discipline(tree, relpath, waivers, findings) -> None:
    """G107 (usage half): ``span(...)`` must be the
    context expression of a ``with`` — any other use (assignment, bare
    expression, argument) skips ``__exit__`` and records nothing."""
    with_ctx_ids: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                with_ctx_ids.add(id(item.context_expr))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in with_ctx_ids:
            continue
        chain = _attr_chain(node.func)
        if not chain or chain[-1] not in _SPAN_FUNCS:
            continue
        # only the tracing API, not unrelated helpers that happen to be
        # named span: require a tracing/tracer qualifier or a bare import
        root = chain[0]
        if len(chain) > 1 and root not in ("tracing", "tracer", "self"):
            continue
        if not _waived("G107", node.lineno, waivers):
            findings.append(Finding(
                "G107", relpath, node.lineno,
                f"{'.'.join(chain)}() used outside a 'with' statement: the "
                "span never __exit__s, so it is never recorded — use "
                "'with tracing.span(...):' (or waive with '# graft: trace-ok')",
            ))


# ------------------------------------------------------------------- G105
_FAULT_POINT_RE = re.compile(r"fault_point\(\s*[\"']([^\"']+)[\"']")
_FAULT_REF_RES = [
    re.compile(r"fault_inject\(\s*[\"']([^\"']+)[\"']"),
    re.compile(r"setenv\(\s*[\"']" + FAULT_ENV + r"[\"']\s*,\s*[\"']([^\"']+)[\"']"),
    re.compile(r"environ\[[\"']" + FAULT_ENV + r"[\"']\]\s*=\s*[\"']([^\"']+)[\"']"),
    re.compile(FAULT_ENV + r"=([\w:,.\[\]\-]+)"),
]


def _spec_points(spec: str) -> Iterable[str]:
    for item in spec.split(","):
        if "[" in item or "]" in item:
            continue  # grammar placeholder (docs: "point[:action]")
        point = item.strip().partition(":")[0]
        if point:
            yield point


def check_fault_registry(repo_root: str) -> List[Finding]:
    """G105: every fault point referenced by tests/ or docs/ must exist as a
    ``fault_point("...")`` call in the package — otherwise the referencing
    test arms a point that can never fire and silently tests nothing."""
    defined: Set[str] = set()
    for path in _walk_py(os.path.join(repo_root, "accelerate_tpu")):
        with open(path, encoding="utf-8") as f:
            defined.update(_FAULT_POINT_RE.findall(f.read()))

    findings: List[Finding] = []
    ref_files = list(_walk_py(os.path.join(repo_root, "tests")))
    ref_files += _walk_suffix(os.path.join(repo_root, "docs"), ".md")
    for path in ref_files:
        rel = os.path.relpath(path, repo_root)
        with open(path, encoding="utf-8") as f:
            text = f.read()
        waivers = parse_waivers(text)
        for i, line in enumerate(text.splitlines(), start=1):
            for ref_re in _FAULT_REF_RES:
                for m in ref_re.finditer(line):
                    for point in _spec_points(m.group(1)):
                        if point in defined:
                            continue
                        if _waived("G105", i, waivers):
                            continue
                        findings.append(Finding(
                            "G105", rel, i,
                            f"fault point {point!r} is referenced here but "
                            "no fault_point() call defines it",
                        ))
    return _dedupe(findings)


# ------------------------------------------------------------ entry points
def _walk_py(root: str) -> Iterable[str]:
    yield from _walk_suffix(root, ".py")


def _walk_suffix(root: str, suffix: str) -> List[str]:
    out: List[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(suffix):
                out.append(os.path.join(dirpath, fn))
    return out


def _dedupe(findings: List[Finding]) -> List[Finding]:
    seen, out = set(), []
    for f in findings:
        key = (f.code, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def lint_package(repo_root: str) -> List[Finding]:
    """Run G101–G105 over the whole package tree."""
    findings: List[Finding] = []
    for path in _walk_py(os.path.join(repo_root, "accelerate_tpu")):
        rel = os.path.relpath(path, repo_root)
        with open(path, encoding="utf-8") as f:
            findings.extend(lint_source(f.read(), rel))
    findings.extend(check_fault_registry(repo_root))
    return _dedupe(findings)
