"""graftcheck Level 5 (accelerate_tpu/analysis/numerics.py): per-rule
fixtures + drift witness + int8 quantization edge cases.

Every rule gets a positive fixture (the checker demonstrably flags it) and
a passing/waived negative. Fixtures build real jitted programs at trivial
shapes; the full-tree numerics run and the full drift witness are
slow-marked — the fast suite runs the witness subset the baseline gates.
"""

import collections
import json
import os
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.analysis import Finding, finding_record, level_of, sarif_report
from accelerate_tpu.analysis import numerics as num
from accelerate_tpu.analysis.lowering import (
    narrow_add_reduces,
    narrow_dot_ops,
    unordered_reduction_inventory,
)
from accelerate_tpu.analysis.numerics import (
    KV_INT8_BOUND,
    check_accumulation,
    check_demoting_aliases,
    check_f64,
    check_loss_output,
    check_quant_scales,
    check_rng_jaxpr,
    check_train_state,
    check_widening_aliases,
    changed_groups,
    compare_accum,
    compare_drift,
    compare_nondeterminism,
    compare_reduce,
    drift_bound,
    lint_rng_package,
    lint_rng_source,
    load_baseline,
    make_numerics_baseline,
    run_drift_witness,
    run_numerics_checks,
)
from accelerate_tpu.analysis.program import ProgramRecord

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASELINE = os.path.join(_ROOT, "runs", "numerics_baseline.json")


def _codes(findings):
    return [f.code for f in findings]


def _src(code: str) -> str:
    return textwrap.dedent(code)


def _record(fn, *args, donated=frozenset(), group="engine.dense", **jit_kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        traced = jax.jit(fn, **jit_kw).trace(*args)
        return ProgramRecord(
            group=group, name="fixture", lowered=traced.lower(),
            donated=set(donated), jaxpr=traced.jaxpr,
        )


class _FakeLowered:
    """Stub for alias-dtype fixtures: real alias syntax, synthetic avals
    (jit never pairs buffers of different dtypes, so the widening/demoting
    cases cannot be built from a live program)."""

    def __init__(self, text, in_avals, out_avals):
        self._text = text
        self.in_avals = in_avals
        self.out_info = out_avals

    def as_text(self):
        return self._text


def _alias_record(in_dtype, out_dtype, donated=frozenset()):
    lowered = _FakeLowered(
        "%arg0: tensor<4xbf16> {tf.aliasing_output = 0}",
        [jax.ShapeDtypeStruct((4,), in_dtype)],
        [jax.ShapeDtypeStruct((4,), out_dtype)],
    )
    return ProgramRecord(group="engine.dense", name="fixture",
                         lowered=lowered, donated=set(donated))


# ---------------------------------------------------------------- G401
def test_g401_flags_f64():
    with jax.enable_x64(True):
        rec = _record(lambda x: x * 2.0, np.zeros(4, np.float64))
    found = check_f64(rec)
    assert _codes(found) == ["G401"] and "f64" in found[0].message


def test_g401_bf16_program_is_clean():
    rec = _record(lambda x: x * 2, jnp.zeros(4, jnp.bfloat16))
    assert check_f64(rec) == []


def test_g401_widening_alias():
    found = check_widening_aliases(_alias_record(jnp.bfloat16, jnp.float32))
    assert _codes(found) == ["G401"] and "widened" in found[0].message


def test_g401_matching_alias_is_clean():
    assert check_widening_aliases(
        _alias_record(jnp.bfloat16, jnp.bfloat16)) == []


# ---------------------------------------------------------------- G402
def test_g402_int8_dot_keeping_narrow_type():
    a, b = jnp.zeros((2, 3), jnp.int8), jnp.zeros((3, 4), jnp.int8)
    rec = _record(lambda a, b: jax.lax.dot(a, b), a, b)
    found, dots, reduces = check_accumulation(rec)
    assert _codes(found) == ["G402"] and "int8/fp8" in found[0].message


def test_g402_int8_dot_accumulating_i32_is_clean():
    a, b = jnp.zeros((2, 3), jnp.int8), jnp.zeros((3, 4), jnp.int8)
    rec = _record(
        lambda a, b: jax.lax.dot(a, b, preferred_element_type=jnp.int32),
        a, b)
    found, dots, reduces = check_accumulation(rec)
    assert found == [] and dots == 0


def test_g402_bf16_dot_counts_into_inventory():
    a, b = jnp.zeros((2, 3), jnp.bfloat16), jnp.zeros((3, 4), jnp.bfloat16)
    rec = _record(lambda a, b: a @ b, a, b)
    found, dots, reduces = check_accumulation(rec)
    assert found == [] and dots == 1  # inventory-gated, not a hard finding
    rec = _record(
        lambda a, b: jnp.matmul(a, b, preferred_element_type=jnp.float32),
        a, b)
    assert check_accumulation(rec)[1] == 0


def test_g402_long_bf16_reduce_is_hard():
    x = jnp.zeros((4, 256), jnp.bfloat16)
    rec = _record(
        lambda x: jax.lax.reduce(x, jnp.bfloat16(0), jax.lax.add, (1,)), x)
    found, dots, reduces = check_accumulation(rec)
    assert _codes(found) == ["G402"] and "256 elements" in found[0].message
    assert reduces == 0


def test_g402_short_bf16_reduce_is_inventory():
    x = jnp.zeros((4, 16), jnp.bfloat16)  # head_dim-sized partial sum
    rec = _record(
        lambda x: jax.lax.reduce(x, jnp.bfloat16(0), jax.lax.add, (1,)), x)
    found, dots, reduces = check_accumulation(rec)
    assert found == [] and reduces == 1


def test_g402_jnp_sum_upcasts_and_is_clean():
    rec = _record(lambda x: jnp.sum(x, axis=1), jnp.zeros((4, 256), jnp.bfloat16))
    found, dots, reduces = check_accumulation(rec)
    assert found == [] and reduces == 0


def test_g402_compare_counts():
    base = {"accum": {"p": 2}, "reduce": {"p": 1}}
    assert compare_accum({"p": 2}, base, "b") == []
    assert compare_accum({"p": 1}, base, "b") == []  # shrinkage passes
    assert _codes(compare_accum({"p": 3}, base, "b")) == ["G402"]
    assert _codes(compare_accum({"new": 1}, base, "b")) == ["G402"]
    assert compare_reduce({"p": 1}, base, "b") == []
    assert _codes(compare_reduce({"p": 2}, base, "b")) == ["G402"]


# ---------------------------------------------------------------- G403
_Moments = collections.namedtuple("_Moments", ["mu", "nu"])


def _state(params_dtype=jnp.float32, mu_dtype=jnp.bfloat16,
           nu_dtype=jnp.float32):
    return {
        "params": {"w": jnp.zeros(2, params_dtype)},
        "opt_state": (_Moments(mu=jnp.zeros(2, mu_dtype),
                               nu=jnp.zeros(2, nu_dtype)),),
    }


def test_g403_policy_conformant_state_is_clean():
    assert check_train_state(_state()) == []


def test_g403_bf16_master_weight():
    found = check_train_state(_state(params_dtype=jnp.bfloat16))
    assert _codes(found) == ["G403"] and "params" in found[0].message


def test_g403_bf16_nu_flagged_mu_allowed():
    found = check_train_state(_state(nu_dtype=jnp.bfloat16))
    assert _codes(found) == ["G403"] and ".nu" in found[0].message


def test_g403_loss_output_dtype():
    rec = _record(lambda x: jnp.sum(x).astype(jnp.bfloat16),
                  jnp.zeros(4), group="train_step")
    assert _codes(check_loss_output(rec)) == ["G403"]
    rec = _record(lambda x: jnp.sum(x), jnp.zeros(4), group="train_step")
    assert check_loss_output(rec) == []


def test_g403_demoting_alias():
    rec = _alias_record(jnp.float32, jnp.bfloat16, donated={0})
    found = check_demoting_aliases(rec)
    assert _codes(found) == ["G403"] and "demoted" in found[0].message
    assert check_demoting_aliases(
        _alias_record(jnp.float32, jnp.float32, donated={0})) == []


def test_g403_repo_quant_scales_are_f32():
    assert check_quant_scales() == []


# ---------------------------------------------------------------- G404
def test_g404_key_reused_by_two_samplers():
    found = lint_rng_source(_src("""
        import jax
        def f(key):
            a = jax.random.uniform(key)
            b = jax.random.normal(key)
            return a + b
    """), "x.py")
    assert _codes(found) == ["G404"] and "second sampler" in found[0].message


def test_g404_split_between_draws_is_clean():
    assert lint_rng_source(_src("""
        import jax
        def f(key):
            key, sub = jax.random.split(key)
            a = jax.random.uniform(sub)
            key, sub = jax.random.split(key)
            return a + jax.random.normal(sub)
    """), "x.py") == []


def test_g404_loop_reuse():
    found = lint_rng_source(_src("""
        import jax
        def f(key):
            out = []
            for i in range(4):
                out.append(jax.random.uniform(key))
            return out
    """), "x.py")
    assert _codes(found) == ["G404"] and "loop" in found[0].message


def test_g404_fold_in_per_iteration_is_clean():
    assert lint_rng_source(_src("""
        import jax
        def f(key):
            out = []
            for i in range(4):
                k = jax.random.fold_in(key, i)
                out.append(jax.random.uniform(k))
            return out
    """), "x.py") == []


def test_g404_waiver_silences():
    assert lint_rng_source(_src("""
        import jax
        def f(key):
            a = jax.random.uniform(key)
            # graft: key-ok
            b = jax.random.normal(key)
            return a + b
    """), "x.py") == []


def test_g404_numpy_rng_not_classified():
    assert lint_rng_source(_src("""
        import numpy as np
        def f(rng):
            for i in range(4):
                x = np.random.uniform(rng)
            return x
    """), "x.py") == []


def test_g404_jaxpr_two_draws_one_key():
    def f(key):
        return jax.random.uniform(key, (2,)) + jax.random.normal(key, (2,))

    rec = _record(f, jax.random.key(0))
    assert _codes(check_rng_jaxpr(rec)) == ["G404"]


def test_g404_jaxpr_split_is_clean():
    def f(key):
        k1, k2 = jax.random.split(key)
        return jax.random.uniform(k1, (2,)) + jax.random.normal(k2, (2,))

    rec = _record(f, jax.random.key(0))
    assert check_rng_jaxpr(rec) == []


def test_g404_repo_rng_lint_is_clean():
    assert lint_rng_package(_ROOT) == []


# ---------------------------------------------------------------- G405
def test_g405_scatter_add_inventoried():
    def f(x, u):
        return x.at[jnp.array([0, 1])].add(u)

    rec = _record(f, jnp.zeros(4), jnp.ones(2))
    inv = unordered_reduction_inventory(rec.lowered.as_text())
    assert inv.get("scatter-add", 0) >= 1


def test_g405_compare_inventory():
    base = {"nondeterminism": {"p": {"scatter-add": 1}}}
    assert compare_nondeterminism({"p": {"scatter-add": 1}}, base, "b") == []
    assert compare_nondeterminism({"p": {}}, base, "b") == []
    grown = compare_nondeterminism({"p": {"scatter-add": 2}}, base, "b")
    assert _codes(grown) == ["G405"]
    unknown = compare_nondeterminism({"q": {"all_reduce": 1}}, base, "b")
    assert _codes(unknown) == ["G405"]


# ---------------------------------------------------------------- drift
def test_drift_bound_rules():
    assert drift_bound("kv.int8_dequant", "max_abs_err_over_amax", 1.0) == \
        KV_INT8_BOUND  # fixed analytic contract, never remeasured
    assert drift_bound("engine.dense", "token_mismatch_fraction", 0.0) == 0.05
    assert drift_bound("engine.dense", "token_mismatch_fraction", 0.9) == 1.0
    assert drift_bound("forward", "max_rel_err", 1e-2) == pytest.approx(4e-2)


def test_compare_drift():
    base = {"drift": {"forward": {"metric": "max_rel_err", "bound": 0.04}}}
    ok = {"forward": {"metric": "max_rel_err", "value": 0.01}}
    assert compare_drift(ok, base, "b") == []
    bad = {"forward": {"metric": "max_rel_err", "value": 0.1}}
    assert _codes(compare_drift(bad, base, "b")) == ["G401"]
    unknown = {"new": {"metric": "max_rel_err", "value": 0.1}}
    assert _codes(compare_drift(unknown, base, "b")) == ["G401"]


def test_witness_fast_subset_within_committed_bounds():
    baseline = load_baseline(_BASELINE)
    assert baseline is not None, "runs/numerics_baseline.json must be committed"
    out = run_drift_witness(["forward", "kv.int8_dequant"])
    for name, rec in out.items():
        bound = baseline["drift"][name]["bound"]
        assert rec["value"] <= bound, (name, rec, bound)


@pytest.mark.slow
def test_witness_full_within_committed_bounds():
    baseline = load_baseline(_BASELINE)
    out = run_drift_witness()
    assert set(out) == set(num.WITNESS_NAMES)
    for name, rec in out.items():
        assert rec["value"] <= baseline["drift"][name]["bound"], (name, rec)


def test_numerics_engine_dense_group_is_clean():
    # one-group lowering keeps the fast suite honest without the full sweep
    assert run_numerics_checks(baseline_path=_BASELINE,
                               groups=["engine.dense"],
                               with_witness=False, repo_root=_ROOT) == []


@pytest.mark.slow
def test_numerics_full_run_is_clean():
    assert run_numerics_checks(baseline_path=_BASELINE,
                               repo_root=_ROOT) == []


def test_missing_baseline_is_a_finding(tmp_path):
    found = run_numerics_checks(
        baseline_path=str(tmp_path / "nope.json"),
        groups=[], with_witness=False, repo_root=_ROOT)
    assert _codes(found) == ["G401"] and "baseline missing" in found[0].message


def test_make_baseline_preserves_reviewed_content():
    prior = {"policy": {"compute": "bfloat16"}, "accum": {"old": 3},
             "waivers": {"G402": [{"pattern": "x", "reason": "r"}]}}
    new = make_numerics_baseline(
        {"accum": {"p": 1},
         "drift": {"forward": {"metric": "max_rel_err", "value": 1e-2}}},
        prior)
    assert new["waivers"] == prior["waivers"]
    assert new["policy"] == prior["policy"]
    assert new["accum"] == {"old": 3, "p": 1}  # partial runs merge
    assert new["drift"]["forward"]["bound"] == pytest.approx(4e-2)


# ---------------------------------------------------------------- changed-only
def test_changed_groups_mapping(monkeypatch):
    monkeypatch.setattr(num, "changed_paths",
                        lambda root: ["accelerate_tpu/spec.py"])
    assert changed_groups(_ROOT) == (["engine.spec"], True)
    monkeypatch.setattr(num, "changed_paths", lambda root: ["README.md"])
    assert changed_groups(_ROOT) == ([], False)
    monkeypatch.setattr(num, "changed_paths",
                        lambda root: ["accelerate_tpu/models/llama.py"])
    assert changed_groups(_ROOT) == (None, True)
    monkeypatch.setattr(num, "changed_paths", lambda root: None)
    assert changed_groups(_ROOT) == (None, True)  # git unusable: run all


# ---------------------------------------------------------------- schema
def test_finding_record_schema():
    rec = finding_record(Finding("G402", "p.py", 3, "msg", program="g/n"))
    assert rec == {"level": "numerics", "rule": "G402", "path": "p.py",
                   "line": 3, "message": "msg", "program": "g/n",
                   "severity": "error", "waiver": None}
    assert level_of("G101") == "host" and level_of("G301") == "concurrency"


def test_sarif_report_schema():
    doc = sarif_report([Finding("G404", "p.py", 7, "msg")])
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftcheck"
    assert any(r["id"] == "G404" for r in run["tool"]["driver"]["rules"])
    res = run["results"][0]
    assert res["ruleId"] == "G404"
    assert res["locations"][0]["physicalLocation"]["region"]["startLine"] == 7
    json.dumps(doc)  # must be serializable as-is


# ---------------------------------------------------------------- int8 edges
def test_kv_quantize_all_zero_block():
    from accelerate_tpu.kvcache import kv_dequantize, kv_quantize

    q, scale = kv_quantize(jnp.zeros((2, 4, 2, 4), jnp.float32))
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(scale))) and np.all(
        np.asarray(scale) > 0)  # floored, no div-by-zero downstream
    assert np.all(np.asarray(kv_dequantize(q, scale, jnp.float32)) == 0)


def test_kv_quantize_denormal_scale_stays_finite():
    from accelerate_tpu.kvcache import kv_dequantize, kv_quantize

    x = jnp.full((2, 4, 2, 4), 1e-30, jnp.float32)
    q, scale = kv_quantize(x)
    deq = np.asarray(kv_dequantize(q, scale, jnp.float32))
    assert np.all(np.isfinite(np.asarray(scale)))
    assert np.all(np.isfinite(deq))
    assert float(np.max(np.abs(deq - np.asarray(x)))) <= 1e-6


def test_kv_quantize_saturation_round_trip():
    from accelerate_tpu.kvcache import kv_dequantize, kv_quantize

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 8, 2, 4)).astype(np.float32)
    x[0, 0, 0, 0] = 100.0  # max-magnitude element pins the amax
    x[1, 0, 0, 0] = -100.0
    q, scale = kv_quantize(jnp.asarray(x))
    assert int(np.max(np.asarray(q))) <= 127
    assert int(np.min(np.asarray(q))) >= -127
    deq = np.asarray(kv_dequantize(q, scale, jnp.float32))
    amax = np.maximum(np.max(np.abs(x), axis=(-1, -2), keepdims=True), 1e-6)
    assert float(np.max(np.abs(x - deq) / amax)) <= KV_INT8_BOUND


@pytest.mark.parametrize("block", [None, 4])
def test_block_quant_all_zero_and_saturation(block):
    from accelerate_tpu.utils.quantization import QuantizedLeaf, _quantize_array

    zeros = np.zeros((8, 4), np.float32)
    q, scales = _quantize_array(zeros, bits=8, block_size=block)
    leaf = QuantizedLeaf(q, jnp.asarray(scales), jnp.float32, block_size=block)
    assert np.all(np.isfinite(np.asarray(scales)))
    assert np.all(np.asarray(leaf.dequantize()) == 0)

    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    x[0, 0] = 50.0
    x[4, 1] = -50.0
    q, scales = _quantize_array(x, bits=8, block_size=block)
    leaf = QuantizedLeaf(q, jnp.asarray(scales), jnp.float32, block_size=block)
    deq = np.asarray(leaf.dequantize())
    amax = float(np.max(np.abs(x)))
    assert float(np.max(np.abs(deq))) <= amax * 1.01  # no overshoot
    assert float(np.max(np.abs(x - deq))) / amax <= 1.0 / 127.0
