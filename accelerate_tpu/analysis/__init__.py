"""graftcheck — static invariant analysis for jitted programs and host code.

Seven PRs of this repo accumulated hard invariants that were only enforced
by runtime tests which must *hit* the violating path: ≤2/≤3 jitted programs
per engine config, one host transfer per train step, donated-vs-carried
arena discipline, the typed error taxonomy, barriers-with-timeout. This
package checks them at the **program** level (AOT-lowered jaxpr/StableHLO
inspection, no TPU needed) and the **host** level (an AST lint with
repo-specific rules), in the spirit of veScale's static SPMD-consistency
verification (arxiv 2509.07003).

Run it as ``python -m accelerate_tpu.analysis`` (or ``make check-static``).

Rules
-----
Level 1 — program analysis (``analysis/program.py``):

* **G001** host-callback / transfer primitive inside a jitted hot program
* **G002** donation correctness: every donated invar aliased to an output,
  and nothing outside the donated arguments aliased (a donated carried
  array would corrupt the deferred-readback ring)
* **G003** weak-typed (python-scalar) operands that fragment the jit cache
* **G004** program-count / collective-inventory drift against the committed
  baseline (``runs/static_baseline.json``)

Level 2 — host lint (``analysis/host.py``):

* **G101** blocking readback on device values in a hot-path module without
  a ``# graft: sync-ok`` waiver
* **G102** coordination wait without a timeout route (bare ``.wait()`` /
  ``.join()``) or anonymous ``wait_for_everyone()`` barrier
* **G103** bare ``RuntimeError``/``Exception`` raise where the
  ``utils/fault.py`` taxonomy has a precise type
* **G104** tracker/metrics I/O while holding the server lock
* **G105** fault-injection point referenced by tests/docs but absent from
  the code's ``fault_point`` registry
* **G107** tracing discipline: host clock / tracer call inside a jitted
  function, or ``tracing.span`` used outside a ``with``
* **G108** metric-name discipline: ``bump``/``gauge``/``observe`` call
  site whose metric name is not a ``[a-z0-9_/]+`` literal (or
  literal-fragment f-string) — computed names fork ad-hoc namespaces
  the exporter and dashboards never see

Level 3 — sharding & memory audit (``analysis/sharding.py``):

* **G201** a large state tensor (param / optimizer moment / KV arena)
  fully replicated while the active ``ParallelismConfig`` claims it is
  sharded
* **G202** GSPMD-inserted reshard collective (all-gather / all-to-all /
  collective-permute) over a mesh axis the declared specs in
  ``parallel/sharding.py`` never imply for that op
* **G203** static per-device HBM footprint growth past the budget in
  ``runs/sharding_baseline.json`` (growth fails, shrinkage passes)
* **G204** collective crossing the slow DCN axis inside a while-loop
  body, trip-count-weighted
* **G205** a large non-donated input whose buffer is dead after the call
  (an output of the same shape/dtype could have reused it)

Level 3 waivers live in ``runs/sharding_baseline.json`` (program-level
findings have no source line to comment on); see docs/static_analysis.md.

Level 4 — host concurrency & gang-safety audit (``analysis/concurrency.py``):

* **G301** lock-order edge (or cycle) outside the baseline DAG committed
  in ``runs/concurrency_baseline.json`` — a potential deadlock; a runtime
  witness (``analysis/witness.py``) asserts the order actually observed
  during the fleet chaos test is a subgraph of the same DAG
* **G302** blocking operation while holding a lock (timeout-less
  ``queue.get``/``Future.result``/``join``/foreign ``wait``,
  ``time.sleep``, blocking device readbacks)
* **G303** shared attribute written from ≥2 thread entrypoints without a
  common guarding lock
* **G304** spawned thread with no join route from its owner's
  close()/drain()
* **G305** bare ``set_result``/``set_exception`` outside the race-safe
  resolver in serving/fleet
* **G306** collective call reachable only under host-local state (rank
  test, filesystem check, caught exception) — gang divergence

Level 5 — numerics, precision & RNG audit (``analysis/numerics.py``):

* **G401** unintended dtype promotion: f64 in a lowered hot program, a
  donated input aliased to a wider output (live HBM silently widened),
  or a bf16-vs-f32 drift-witness value outside its committed bound
* **G402** accumulation-dtype discipline: int8/fp8 dots keeping the
  narrow result type and LONG bf16/f16 add-reduces (>128 reduced
  elements) are hard findings; the counts of bf16-accumulating dots
  and of short bf16 add-reduces are inventory-gated per program
* **G403** state-dtype contract: master weights, optimizer moments
  (modulo the declared ``mu`` policy), the loss scalar, and every
  quantization scale must be f32
* **G404** RNG-key discipline: a key consumed by two samplers, or
  consumed in a loop without per-iteration split/fold_in (AST), or a
  program with ≥2 random draws and zero split/fold_in (jaxpr)
* **G405** non-determinism inventory: unordered-reduction ops
  (scatter-add, select_and_scatter, cross-replica reduces) gated
  against the committed per-program inventory

Level 5 baselines, drift bounds, and program-scoped waivers live in
``runs/numerics_baseline.json``.

Level 6 — static performance audit (``analysis/perf.py``):

* **G501** per-program roofline budgets: predicted step time, MFU floor,
  and decode tokens-per-second vs ``runs/perf_baseline.json`` (growth
  fails, improvement passes and invites re-baseline); an ordering
  witness executes the tiny engines + train steps and asserts the
  predictor's A/B ordering matches measured walltime ordering
* **G502** unoverlapped collective: trip-count-weighted collective on
  the critical path not lowered as an ``async-start``/``-done`` pair, or
  a DCN-crossing collective whose modeled transfer exceeds the
  independent compute available to hide it
* **G503** padding/bucket waste: fraction of dot FLOPs spent on padded
  rows (pow-2 prompt buckets, (slots, max_len) arena vs live tokens),
  gated per program
* **G504** fusion/kernel inventory: fusion count + dominant-op histogram
  per program gated vs baseline (static fusion-break detector)
* **G505** pipeline bubble-fraction budgets from the static
  1F1B/interleaved schedule model shared with
  ``benchmarks/pp_schedule_bench.py``

Level 6 budgets and program-scoped waivers live in
``runs/perf_baseline.json``.

Waivers are line-scoped comments, same line or the line above:
``# graft: sync-ok`` (G101), ``# graft: wait-ok`` (G102),
``# graft: raise-ok`` (G103), ``# graft: lock-ok`` (G104),
``# graft: fault-ok`` (G105), ``# graft: trace-ok`` (G107),
``# graft: metric-ok`` (G108), ``# graft: block-ok`` (G302),
``# graft: race-ok`` (G303), ``# graft: thread-ok`` (G304),
``# graft: resolve-ok`` (G305), ``# graft: gang-ok`` (G306),
``# graft: key-ok`` (G404), or the universal ``# graft: GXXX-ok``.
G301 is edge-scoped — its waivers live in the baseline JSON like
Level 3's; G401-G405 program-scoped waivers live in the numerics
baseline. See ``docs/static_analysis.md`` for the full table and
re-baselining.
"""

from __future__ import annotations

import dataclasses

RULES = {
    "G001": "host-callback/transfer primitive inside a jitted program",
    "G002": "donation aliasing broken or a non-donated operand aliased",
    "G003": "weak-typed operand fragments the jit cache",
    "G004": "program-count/collective inventory drifted from baseline",
    "G101": "blocking readback in a hot-path module without a waiver",
    "G102": "coordination wait without a timeout route / anonymous barrier",
    "G103": "untyped raise where a fault-taxonomy type exists",
    "G104": "tracker/metrics call while holding the server lock",
    "G105": "referenced fault-injection point missing from the registry",
    "G107": "tracer/clock call in jitted code or span used outside 'with'",
    "G108": "metric name is not a [a-z0-9_/]+ literal (namespace discipline)",
    "G201": "large state tensor replicated where the config claims sharding",
    "G202": "GSPMD reshard collective not implied by the declared specs",
    "G203": "static per-device HBM footprint grew past the committed budget",
    "G204": "collective crosses the DCN axis inside a while-loop body",
    "G205": "large non-donated input dead after the call (missed donation)",
    "G301": "lock-order edge/cycle outside the committed DAG (deadlock risk)",
    "G302": "blocking operation while holding a lock",
    "G303": "shared attribute written from ≥2 threads without a common lock",
    "G304": "spawned thread has no join route from its owner's close/drain",
    "G305": "bare set_result/set_exception outside the race-safe resolver",
    "G306": "collective reachable only under host-local state (gang split)",
    "G401": "unintended dtype promotion (f64 / widened alias / drift bound)",
    "G402": "narrow matmul or reduction without f32 accumulation",
    "G403": "master state, loss, or quantization scale not f32",
    "G404": "PRNG key reused or consumed without split/fold_in",
    "G405": "unordered-reduction op outside the committed inventory",
    "G501": "roofline step-time/MFU/tokens-per-second budget regressed",
    "G502": "collective on the critical path that the schedule cannot hide",
    "G503": "padded-row dot-FLOP fraction grew past the committed budget",
    "G504": "fusion/kernel inventory drifted from baseline (fusion break)",
    "G505": "pipeline bubble fraction grew past the committed budget",
}

# rule-code century -> level name (the unified --json/--sarif schema key)
_LEVELS = {"G0": "program", "G1": "host", "G2": "sharding",
           "G3": "concurrency", "G4": "numerics", "G5": "perf"}


def level_of(code: str) -> str:
    return _LEVELS.get(code[:2], "unknown")


def finding_record(f: "Finding", waiver: str = None) -> dict:
    """One finding in the unified machine-readable schema shared by every
    level (satellite of ISSUE 12): level, rule, path, line, message,
    program, severity, waiver."""
    return {
        "level": level_of(f.code),
        "rule": f.code,
        "path": f.path,
        "line": f.line,
        "message": f.message,
        "program": f.program,
        "severity": "error",
        "waiver": waiver,
    }


def sarif_report(findings) -> dict:
    """SARIF 2.1.0 document for CI annotation (one run, tool `graftcheck`)."""
    return {
        "$schema": "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                   "master/Schemata/sarif-schema-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftcheck",
                "informationUri": "docs/static_analysis.md",
                "rules": [
                    {"id": code,
                     "shortDescription": {"text": text},
                     "properties": {"level": level_of(code)}}
                    for code, text in sorted(RULES.items())
                ],
            }},
            "results": [
                {
                    "ruleId": f.code,
                    "level": "error",
                    "message": {"text": f.message},
                    "locations": [{
                        "physicalLocation": {
                            "artifactLocation": {"uri": f.path},
                            "region": {"startLine": max(f.line, 1)},
                        },
                    }],
                    "properties": {"program": f.program,
                                   "graftcheckLevel": level_of(f.code)},
                }
                for f in findings
            ],
        }],
    }


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str  # rule id, e.g. "G101"
    path: str  # repo-relative file, or a program name for Level 1
    line: int  # 1-based; 0 when the finding is not line-addressable
    message: str
    # stable lowered-program name ("train.fsdp8/fused_train_step",
    # "engine.paged/decode_step") for program-scoped findings — empty for
    # host-lint findings. Serialized in --json so CI diffs key on it.
    program: str = ""

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: {self.code} {self.message}"


__all__ = ["Finding", "RULES", "level_of", "finding_record", "sarif_report"]
