"""quantlint self-tests.

Every AST rule and every flow invariant must catch its seeded fixture
violation (tests/fixtures/quantlint/), and the real src/ tree plus the
default dtype-flow suite must pass clean — the same gate scripts/ci.sh runs.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (FLOW_RULES, RULES, TraceSpec, check_suite,
                            check_trace, lint_file, lint_paths)
from repro.analysis.suite import default_specs
from repro.core.quant import qtypes

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "quantlint"


def lint_fixture(name, rules=None):
    p = FIXTURES / name
    return lint_file(p, rel=str(p), rules=rules)


def rule_ids(findings):
    return sorted({f.rule for f in findings})


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# AST rules: each fixture violation is caught
# ---------------------------------------------------------------------------

def test_rule_registry_complete():
    assert set(RULES) == {"pallas-compiler-params", "raw-compiler-params",
                          "magic-quant-literal", "no-float64",
                          "pallas-interpret"}
    assert set(FLOW_RULES) == {"int8-accum", "scale-once", "scale-mismatch",
                               "packed-int4-upcast", "nonlinear-on-unscaled"}


def test_pallas_compiler_params_rule():
    got = lint_fixture("bad_compiler_params.py",
                       rules=["pallas-compiler-params"])
    # one pallas_call with no compiler_params=, one built without the shim
    assert len(got) == 2
    assert rule_ids(got) == ["pallas-compiler-params"]


def test_raw_compiler_params_rule():
    got = lint_fixture("bad_compiler_params.py",
                       rules=["raw-compiler-params"])
    assert len(got) == 1
    assert "TPUCompilerParams" in got[0].message


def test_magic_quant_literal_rule():
    got = lint_fixture("bad_magic_literal.py", rules=["magic-quant-literal"])
    # -128 and 127 clip bounds, the int4 denominator 15, and 127.0
    assert len(got) == 4
    msgs = " ".join(f.message for f in got)
    for spelling in ("-128", "127", "15", "127.0"):
        assert spelling in msgs
    # positive bare 128 (MXU tile size) must NOT be flagged
    assert not any("128'" in f.message and "-" not in f.message for f in got)


def test_no_float64_rule():
    got = lint_fixture("bad_float64.py", rules=["no-float64"])
    # jnp.float64 attr, "float64" string, np.float64 attr
    assert len(got) == 3


def test_pallas_interpret_rule():
    got = lint_fixture("kernels/bad_interpret.py", rules=["pallas-interpret"])
    # one pallas_call without interpret=, one hardcoded without a wrapper
    # parameter; good_wrapper is clean
    assert len(got) == 2
    assert all(f.line < 40 for f in got), got


def test_pallas_interpret_rule_is_path_scoped():
    # the same rule stays silent outside kernels/ trees
    got = lint_fixture("bad_compiler_params.py", rules=["pallas-interpret"])
    assert got == []


def test_suppression_comments():
    assert lint_fixture("suppressed_ok.py") == []
    # sanity: the same code without the trailing comments would be flagged
    src = (FIXTURES / "suppressed_ok.py").read_text()
    stripped = "\n".join(line.split("#")[0] for line in src.splitlines())
    tmp = FIXTURES / "_stripped_tmp.py"
    tmp.write_text(stripped)
    try:
        got = lint_file(tmp, rel=str(tmp))
        assert "magic-quant-literal" in rule_ids(got)
        assert "no-float64" in rule_ids(got)
    finally:
        tmp.unlink()


def test_clean_pass_on_real_src():
    got = lint_paths([str(REPO / "src")])
    assert got == [], "\n".join(f.format() for f in got)


# ---------------------------------------------------------------------------
# Flow invariants: each seeded trace violation is caught
# ---------------------------------------------------------------------------

def test_flow_int8_accum():
    def bad(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))

    spec = TraceSpec("fix_int8_accum", bad,
                     (_sds((8, 16), jnp.int8), _sds((16, 8), jnp.int8)), {})
    assert "int8-accum" in rule_ids(check_trace(spec))

    def good(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    spec = TraceSpec("fix_int8_accum_ok", good,
                     (_sds((8, 16), jnp.int8), _sds((16, 8), jnp.int8)), {})
    assert check_trace(spec) == []


def test_flow_scale_free_escape():
    def bad(q):
        return q.astype(jnp.float32)

    spec = TraceSpec("fix_escape", bad, (_sds((4, 4), jnp.int8),),
                     {0: "quant"})
    got = check_trace(spec)
    assert rule_ids(got) == ["scale-once"]
    assert "never applied" in got[0].message


def test_flow_double_scaling():
    def bad(q, s):
        return q.astype(jnp.float32) * s * s

    args = (_sds((4, 4), jnp.int8), _sds((4, 1), jnp.float32))
    spec = TraceSpec("fix_double", bad, args, {0: "quant", 1: "scale"})
    got = check_trace(spec)
    assert "scale-once" in rule_ids(got)
    assert any("double-scal" in f.message for f in got)

    def good(q, s):
        return q.astype(jnp.float32) * s

    spec = TraceSpec("fix_double_ok", good, args, {0: "quant", 1: "scale"})
    assert check_trace(spec) == []


def test_flow_scale_mismatch():
    def bad(q, s):
        dequantized = q.astype(jnp.float32) * s
        return dequantized + q.astype(jnp.float32)

    args = (_sds((4, 4), jnp.int8), _sds((4, 1), jnp.float32))
    spec = TraceSpec("fix_mismatch", bad, args, {0: "quant", 1: "scale"})
    assert "scale-mismatch" in rule_ids(check_trace(spec))


def test_flow_packed_int4_upcast():
    def bad(p):
        return p.astype(jnp.float32)

    spec = TraceSpec("fix_packed", bad, (_sds((8, 8), jnp.int8),),
                     {0: "packed"})
    assert "packed-int4-upcast" in rule_ids(check_trace(spec))

    def good(p, s):
        lo = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(p, jnp.int8(4)), jnp.int8(4))
        return lo.astype(jnp.float32) * s

    args = (_sds((8, 8), jnp.int8), _sds((8, 1), jnp.float32))
    spec = TraceSpec("fix_packed_ok", good, args, {0: "packed", 1: "scale"})
    assert check_trace(spec) == []

    # widening to int32 ahead of the shifts (the kernel-safe unpack) is
    # legal for int8 and uint8 storage alike
    def widened(p, s):
        lo, hi = qtypes.int4_nibbles(p)
        return jnp.concatenate([lo, hi], -1).astype(jnp.float32) * s

    for dt in (jnp.int8, jnp.uint8):
        args = (_sds((8, 8), dt), _sds((8, 1), jnp.float32))
        spec = TraceSpec("fix_packed_widened_ok", widened, args,
                         {0: "packed", 1: "scale"})
        assert check_trace(spec) == []


def test_flow_nonlinear_on_unscaled():
    def bad(q):
        return jnp.exp(q.astype(jnp.float32))

    spec = TraceSpec("fix_nonlinear", bad, (_sds((4, 4), jnp.int8),),
                     {0: "quant"})
    assert "nonlinear-on-unscaled" in rule_ids(check_trace(spec))


def test_flow_kernel_suite_clean():
    # the fast suite: ref oracles + jitted Pallas kernels for int8 GEMM,
    # w4a8 GEMM and paged-attention dequant (model-level traces run in CI
    # via `python -m repro.analysis src`)
    got = check_suite(default_specs(fast=True))
    assert got == [], "\n".join(f.format() for f in got)


# ---------------------------------------------------------------------------
# The latent-attention + expert step keeps the W8A8 contract
# ---------------------------------------------------------------------------

def _dots(jaxpr):
    """(lhs dtype, rhs dtype) of every dot_general in a jaxpr, nested ones
    (scans, jits, maps) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(tuple(str(v.aval.dtype) for v in eqn.invars))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _dots(inner)
    return out


def test_flow_mla_moe_step_is_clean_and_int8_into_the_experts():
    """The mixed step of openPangu-Ultra-MoE's block (the default suite's
    `serving_mla_moe_step`) passes every rule, its expert GEMMs take int8
    operands, and its router is a float32 product."""
    from repro.analysis.suite import spec_serving_mla_moe_step
    spec = spec_serving_mla_moe_step()
    assert spec.name in {s.name for s in default_specs()}
    got = check_suite([spec])
    assert got == [], "\n".join(f.format() for f in got)
    dots = _dots(jax.make_jaxpr(spec.fn)(*spec.args).jaxpr)
    assert ("int8", "int8") in dots
    assert ("float32", "float32") in dots
