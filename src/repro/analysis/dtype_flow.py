"""jaxpr dtype-flow checker: the quant arithmetic contract, machine-checked.

Traces a representative quantized computation with `jax.make_jaxpr` and
walks the jaxpr with a small abstract interpreter. Every variable carries a
`Flow` state:

  * ``d`` — the *scale balance*: each quantized operand contributes -1
    (one dequant scale still owed); each multiplication by a scale
    contributes +1; a properly dequantized float tensor sits at 0.
  * ``scale`` — the variable is (derived from) a quantization scale. Scales
    are recognized from input tags or in-graph derivation: ``reduce_max`` of
    ``abs(data)`` (the paper's absmax) followed by elementwise arithmetic.
  * ``packed`` — the variable holds nibble-packed int4 storage; only
    arithmetic shifts (sign-extending unpack) may consume it.
  * ``data`` — the variable descends from quantized data. It survives
    dequantization to d = 0, so applying a scale to already-dequantized
    data is read as double-scaling, not scale arithmetic.

Checked invariants:
  * int8-accum        — every int8 x int8 `dot_general` (including inside
    Pallas kernel bodies) accumulates in int32 or float32 via
    `preferred_element_type`, never in int8/bf16.
  * scale-once        — every int8 -> float path applies its dequant
    scale(s) exactly once: a float graph output with d < 0 escaped without
    dequantization; any data tensor reaching d > 0 was double-scaled.
  * scale-mismatch    — add-like ops never combine tensors at different
    scale states (e.g. an int32 accumulator with a dequantized float).
  * packed-int4-upcast — packed int4 storage is never converted to a float
    dtype or fed to a matmul before the shift-based unpack (an integer
    widening ahead of the shifts is the unpack itself, and legal).
  * nonlinear-on-unscaled — transcendental ops never consume a tensor that
    still owes a dequant scale.

The walker descends through pjit/scan/while/cond/custom_* calls. Pallas
kernel bodies are only scanned structurally (the int8-accum check); their
value flow is checked via the `ref.py` oracles, which tests pin the kernels
to. Closed-over constants are treated as neutral: the contract surface of a
trace is its explicitly tagged arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding

from jax.extend import core as _core

try:
    from jax._src import source_info_util as _siu
except ImportError:  # pragma: no cover
    _siu = None


# ---------------------------------------------------------------------------
# Flow lattice
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Flow:
    d: int = 0            # scale balance (-1 per pending dequant scale)
    scale: bool = False   # is (derived from) a quant scale
    packed: bool = False  # nibble-packed int4 storage
    absval: bool = False  # |data| (absmax precursor)
    data: bool = False    # came from quantized data (survives dequant to d=0,
                          # so `dequantized * scale` reads as double-scaling
                          # rather than scale arithmetic)


NEUTRAL = Flow()


def _strong(f: Flow) -> bool:
    return f.d != 0 or f.scale or f.packed or f.data


def _join(a: Flow, b: Flow) -> Flow:
    if not _strong(a):
        return b
    return a


def _arith_scale(a: Flow, b: Flow) -> bool:
    """mul/div result stays a scale when both operands are scales or a
    scale meets a neutral constant (e.g. `2.0 * absmax / (2^n - 1)`)."""
    if a.scale and b.scale:
        return True
    return (a.scale and not _strong(b)) or (b.scale and not _strong(a))


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One representative graph plus the quant contract of its inputs.

    ``tags`` maps flat argument-leaf index -> "quant" | "packed" | "scale"
    (untagged leaves are neutral). Build specs via `repro.analysis.suite`.
    """

    name: str
    fn: Callable
    args: tuple
    tags: Dict[int, str]


_ELEMENTWISE_PASS = {
    "neg", "sign", "floor", "ceil", "round", "real", "imag", "copy",
    "stop_gradient", "reduce_precision", "convert_element_type",
    "sharding_constraint", "device_put", "is_finite",
}
_STRUCTURAL_PASS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "rev", "gather", "pad", "expand_dims", "cumsum",
    "cummax", "cummin", "sort", "split",
}
_NONLINEAR = {
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "logistic", "erf",
    "erfc", "sin", "cos", "tan", "rsqrt", "sqrt", "cbrt",
}
_ADD_LIKE = {
    "add", "sub", "max", "min", "select_n", "concatenate",
    "dynamic_update_slice", "scatter", "scatter-add", "scatter-mul",
    "scatter-max", "scatter-min", "clamp", "nextafter",
}
_NEUTRAL_OUT = {
    "eq", "ne", "lt", "le", "gt", "ge", "iota", "argmax", "argmin",
    "reduce_and", "reduce_or", "not", "rng_bit_generator", "random_bits",
    "random_seed", "random_wrap", "random_unwrap",
}
_SHIFTS = {"shift_left", "shift_right_arithmetic", "shift_right_logical"}
_REDUCE_PASS = {"reduce_sum", "reduce_prod", "reduce_min", "cumlogsumexp"}


def _eqn_loc(eqn) -> str:
    if _siu is not None:
        try:
            return _siu.summarize(eqn.source_info)
        except Exception:
            pass
    return "<unknown>"


def _float_dtype(aval) -> bool:
    return jnp.issubdtype(aval.dtype, jnp.floating)


def _sub_jaxprs(obj):
    """Yield every Jaxpr reachable from an eqn param value."""
    if isinstance(obj, _core.Jaxpr):
        yield obj
    elif isinstance(obj, _core.ClosedJaxpr):
        yield obj.jaxpr
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            yield from _sub_jaxprs(o)


class _FlowChecker:
    def __init__(self, trace_name: str):
        self.trace = trace_name
        self.findings: List[Finding] = []

    # -- findings ----------------------------------------------------------
    def _emit(self, rule: str, eqn, message: str):
        self.findings.append(Finding(
            f"<trace:{self.trace}>", 0, rule,
            f"{message} (at {_eqn_loc(eqn)})"))

    # -- structural int8-accum check (descends everywhere, incl. pallas) ---
    def check_dots(self, jaxpr: _core.Jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                dts = [v.aval.dtype for v in eqn.invars]
                if all(dt == jnp.int8 for dt in dts):
                    out = eqn.outvars[0].aval.dtype
                    if out not in (jnp.int32, jnp.float32):
                        self._emit(
                            "int8-accum", eqn,
                            "int8 x int8 dot_general accumulates in "
                            f"{out}; pass preferred_element_type="
                            "int32 (or float32)")
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    self.check_dots(sub)

    # -- value-flow interpretation -----------------------------------------
    def run(self, closed: _core.ClosedJaxpr,
            in_flows: Sequence[Flow]) -> List[Flow]:
        jaxpr = closed.jaxpr
        env: Dict[object, Flow] = {v: NEUTRAL for v in jaxpr.constvars}
        assert len(jaxpr.invars) == len(in_flows), \
            (len(jaxpr.invars), len(in_flows))
        env.update(zip(jaxpr.invars, in_flows))

        def get(v) -> Flow:
            if isinstance(v, _core.Literal):
                return NEUTRAL
            return env.get(v, NEUTRAL)

        for eqn in jaxpr.eqns:
            outs = self._eval_eqn(eqn, [get(v) for v in eqn.invars])
            for v, f in zip(eqn.outvars, outs):
                env[v] = f
        return [get(v) for v in jaxpr.outvars]

    def _run_inner(self, inner, eqn, ins: Sequence[Flow]) -> List[Flow]:
        if isinstance(inner, _core.Jaxpr):
            inner = _core.ClosedJaxpr(inner, ())
        n = len(inner.jaxpr.invars)
        # align on the tail: some call prims prepend consts to invars
        flows = list(ins)[-n:] if len(ins) >= n \
            else [NEUTRAL] * (n - len(ins)) + list(ins)
        return self.run(inner, flows)

    def _combine(self, eqn, ins: Sequence[Flow]) -> Flow:
        """add/select/concat/scatter-like: all strong operands must agree."""
        strong = [f for f in ins if _strong(f)]
        ds = {f.d for f in strong if not f.scale}
        if len(ds) > 1:
            self._emit(
                "scale-mismatch", eqn,
                f"{eqn.primitive.name} combines tensors at different scale "
                f"states (balances {sorted(ds)}); apply dequant scales "
                "consistently before mixing")
        out = NEUTRAL
        for f in strong:
            out = _join(out, f)
        return dataclasses.replace(out, absval=False)

    def _eval_eqn(self, eqn, ins: Sequence[Flow]) -> List[Flow]:
        p = eqn.primitive.name
        n_out = len(eqn.outvars)

        # -- higher-order primitives ---------------------------------------
        if p == "scan":
            return self._eval_scan(eqn, ins)
        if p in ("while", "while_loop"):
            return self._eval_while(eqn, ins)
        if p == "cond":
            branches = eqn.params["branches"]
            outs = None
            for br in branches:
                o = self._run_inner(br, eqn, ins[1:])
                outs = o if outs is None else [_join(a, b)
                                               for a, b in zip(outs, o)]
            return outs or [NEUTRAL] * n_out
        if p == "pallas_call":
            # bodies operate on Refs; value flow is validated on the ref
            # oracles instead. Outputs: neutral.
            return [NEUTRAL] * n_out
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                inner = eqn.params[key]
                if isinstance(inner, (_core.Jaxpr, _core.ClosedJaxpr)):
                    return self._run_inner(inner, eqn, ins)

        # -- arithmetic ----------------------------------------------------
        if p == "mul" or p == "dot_general":
            a, b = ins[0], ins[1]
            for f in (a, b):
                if f.packed:
                    self._emit(
                        "packed-int4-upcast", eqn,
                        f"packed int4 storage consumed by {p} before "
                        "shift-based unpack")
            sc = _arith_scale(a, b)
            out = Flow(d=a.d + b.d, scale=sc,
                       data=(a.data or b.data) and not sc)
            if out.d > 0 and not out.scale:
                self._emit(
                    "scale-once", eqn,
                    f"double-scaling: {p} leaves a data tensor with "
                    f"scale balance +{out.d} (a dequant scale applied "
                    "more than once)")
            return [out] * n_out
        if p == "div":
            a, b = ins[0], ins[1]
            sc = _arith_scale(a, b)
            # dividing by a scale quantizes: the result is data again
            data = not sc and (a.data or b.data or b.scale)
            return [Flow(d=a.d - b.d, scale=sc, data=data)] * n_out
        if p == "integer_pow":
            y = eqn.params.get("y", 1)
            return [Flow(d=ins[0].d * y, scale=ins[0].scale)] * n_out
        if p == "abs":
            f = ins[0]
            return [dataclasses.replace(
                f, absval=(f.d == 0 and not f.scale))] * n_out
        if p in ("reduce_max", "reduce_min") and ins[0].absval:
            return [Flow(d=ins[0].d + 1, scale=True)] * n_out
        if p in ("reduce_max", "reduce_min") or p in _REDUCE_PASS:
            return [dataclasses.replace(ins[0], absval=False)] * n_out
        if p in _SHIFTS:
            if ins[0].packed:  # sign-extending unpack -> int4 values
                return [Flow(d=-1, data=True)] * n_out
            return [ins[0]] * n_out
        if p in ("and", "or", "xor"):
            return [_join(ins[0], ins[1])] * n_out
        if p in _NONLINEAR:
            f = ins[0]
            if f.d != 0 and not f.scale:
                self._emit(
                    "nonlinear-on-unscaled", eqn,
                    f"{p} applied to a tensor that still owes "
                    f"{-f.d} dequant scale(s)")
            return [Flow(d=f.d, scale=f.scale)] * n_out
        if p == "clamp":
            return [ins[1]] * n_out
        if p == "convert_element_type":
            f = ins[0]
            new = eqn.outvars[0].aval.dtype
            # an integer widening keeps the nibbles intact (the shifts that
            # follow still unpack them); any other conversion reads the
            # packed byte as one number
            if f.packed and not jnp.issubdtype(new, jnp.integer):
                self._emit(
                    "packed-int4-upcast", eqn,
                    f"packed int4 storage converted to {new} before "
                    "shift-based unpack (nibbles silently reinterpreted)")
                f = dataclasses.replace(f, packed=False)
            return [f] * n_out
        if p in _ADD_LIKE:
            return [self._combine(eqn, ins)] * n_out
        if p in _NEUTRAL_OUT:
            return [NEUTRAL] * n_out
        if p in _ELEMENTWISE_PASS or p in _STRUCTURAL_PASS:
            return [ins[0] if ins else NEUTRAL] * n_out

        # default: propagate the strongest input, flag nothing
        out = NEUTRAL
        for f in ins:
            out = _join(out, f)
        return [dataclasses.replace(out, absval=False)] * n_out

    def _eval_scan(self, eqn, ins: Sequence[Flow]) -> List[Flow]:
        closed = eqn.params["jaxpr"]
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        consts = list(ins[:nc])
        carry = list(ins[nc:nc + ncar])
        xs = list(ins[nc + ncar:])
        outs: List[Flow] = []
        for _ in range(3):  # tiny fixpoint over the carry
            outs = self._run_inner(closed, eqn, consts + carry + xs)
            carry_out = outs[:ncar]
            if carry_out == carry:
                break
            carry = [_join(a, b) for a, b in zip(carry_out, carry)]
        return outs

    def _eval_while(self, eqn, ins: Sequence[Flow]) -> List[Flow]:
        body = eqn.params["body_jaxpr"]
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        consts = list(ins[cn:cn + bn])
        carry = list(ins[cn + bn:])
        for _ in range(3):
            outs = self._run_inner(body, eqn, consts + carry)
            if outs == carry:
                break
            carry = [_join(a, b) for a, b in zip(outs, carry)]
        return carry


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

_TAG_FLOWS = {
    "quant": Flow(d=-1, data=True),
    "packed": Flow(d=-1, packed=True, data=True),
    "scale": Flow(d=1, scale=True),
}


def check_trace(spec: TraceSpec) -> List[Finding]:
    """Trace `spec.fn(*spec.args)` and check the quant dtype contract."""
    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    checker = _FlowChecker(spec.name)
    checker.check_dots(closed.jaxpr)

    in_flows = []
    for i, _ in enumerate(closed.jaxpr.invars):
        tag = spec.tags.get(i)
        in_flows.append(_TAG_FLOWS.get(tag, NEUTRAL) if tag else NEUTRAL)
    out_flows = checker.run(closed, in_flows)

    for i, (var, f) in enumerate(zip(closed.jaxpr.outvars, out_flows)):
        if f.scale or not _float_dtype(var.aval):
            continue  # scales and integer storage legitimately carry debt
        if f.d < 0:
            checker.findings.append(Finding(
                f"<trace:{spec.name}>", 0, "scale-once",
                f"float output #{i} escaped with {-f.d} dequant scale(s) "
                "never applied (scale-free int8->float path)"))
        elif f.d > 0:
            checker.findings.append(Finding(
                f"<trace:{spec.name}>", 0, "scale-once",
                f"float output #{i} is double-scaled (balance +{f.d})"))
    return sorted(set(checker.findings))


def check_suite(specs: Sequence[TraceSpec]) -> List[Finding]:
    out: List[Finding] = []
    for spec in specs:
        out.extend(check_trace(spec))
    return out


FLOW_RULES = {
    "int8-accum": "int8 x int8 matmuls accumulate in int32/f32 via "
                  "preferred_element_type",
    "scale-once": "each dequant scale applied exactly once on every "
                  "int8->float path",
    "scale-mismatch": "no mixing of tensors at different scale states",
    "packed-int4-upcast": "packed int4 never upcast before shift-unpack",
    "nonlinear-on-unscaled": "no transcendental on not-yet-dequantized data",
}
