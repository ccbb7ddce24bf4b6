"""AST-based repo linter: quantization invariants as machine-checked rules.

Each rule inspects one parsed file and yields `Finding`s. Rules live in a
pluggable registry — add one with the `@rule(...)` decorator and it is
picked up by the CLI, `--list-rules`, and the fixture tests automatically.

Suppression: append `# quantlint: disable=<rule-id>[,<rule-id>...]` to the
offending line, or put `# quantlint: disable-file=<rule-id>[,...]` on any
line to silence a rule for the whole file.

Enforced invariants (see README "Static analysis"):
  * pallas-compiler-params — every `pl.pallas_call` passes `compiler_params=`
    built via `repro.kernels.tpu_compiler_params`.
  * raw-compiler-params   — no direct `pltpu.TPUCompilerParams(...)` /
    `pltpu.CompilerParams(...)` construction outside `repro.kernels`.
  * magic-quant-literal   — no bare -128 / -127 / 127 / 15 quant-range
    literals outside `core/quant/qtypes.py`; use `qmin(bits)` / `qmax(bits)`.
  * no-float64            — no float64 dtypes (TPU pipeline is f32/bf16/int).
  * pallas-interpret      — every kernel wrapper plumbs an `interpret=`
    escape hatch into its `pallas_call` (CPU/CI execution path).
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, Iterable, Iterator, List, Optional

from repro.analysis.findings import Finding

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    summary: str
    check: Callable[["FileCtx"], Iterable[Finding]]


RULES: Dict[str, Rule] = {}


def rule(rule_id: str, summary: str):
    """Register a rule. The decorated function maps FileCtx -> Findings."""

    def deco(fn):
        assert rule_id not in RULES, f"duplicate rule id {rule_id!r}"
        RULES[rule_id] = Rule(rule_id, summary, fn)
        return fn

    return deco


# ---------------------------------------------------------------------------
# Per-file context (parse once, share across rules)
# ---------------------------------------------------------------------------

_DISABLE_LINE = re.compile(r"#\s*quantlint:\s*disable=([\w,\- ]+)")
_DISABLE_FILE = re.compile(r"#\s*quantlint:\s*disable-file=([\w,\- ]+)")


class FileCtx:
    def __init__(self, path: Path, source: str, rel: Optional[str] = None):
        self.path = path
        self.rel = (rel or str(path)).replace("\\", "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent
        self._file_disabled = set()
        self._line_disabled: Dict[int, set] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _DISABLE_FILE.search(line)
            if m:
                self._file_disabled |= {r.strip() for r in m.group(1).split(",")}
                continue
            m = _DISABLE_LINE.search(line)
            if m:
                self._line_disabled[i] = {r.strip() for r in m.group(1).split(",")}

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def enclosing_functions(self, node: ast.AST) -> List[ast.FunctionDef]:
        out = []
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(cur)
            cur = self.parent(cur)
        return out

    def suppressed(self, rule_id: str, line: int) -> bool:
        return (rule_id in self._file_disabled
                or rule_id in self._line_disabled.get(line, set()))

    def in_tree(self, *suffixes: str) -> bool:
        return any(self.rel.endswith(s) for s in suffixes)

    def finding(self, rule_id: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 0)
        if not self.suppressed(rule_id, line):
            yield Finding(self.rel, line, rule_id, message)


def _callee_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _kw(call: ast.Call, name: str) -> Optional[ast.keyword]:
    for k in call.keywords:
        if k.arg == name:
            return k
    return None


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

_SHIM_FILE = "repro/kernels/__init__.py"
_QTYPES_FILE = "repro/core/quant/qtypes.py"


@rule("pallas-compiler-params",
      "pl.pallas_call must pass compiler_params= built via the "
      "repro.kernels.tpu_compiler_params shim")
def _check_pallas_compiler_params(ctx: FileCtx) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _callee_name(node) == "pallas_call"):
            continue
        kw = _kw(node, "compiler_params")
        if kw is None:
            yield from ctx.finding(
                "pallas-compiler-params", node,
                "pallas_call without compiler_params= (build them via "
                "repro.kernels.tpu_compiler_params)")
        elif not (isinstance(kw.value, ast.Call)
                  and _callee_name(kw.value) == "tpu_compiler_params"):
            yield from ctx.finding(
                "pallas-compiler-params", kw.value,
                "compiler_params not built via repro.kernels."
                "tpu_compiler_params (the one construction point)")


@rule("raw-compiler-params",
      "no pltpu.TPUCompilerParams / pltpu.CompilerParams construction "
      "outside the repro.kernels shim")
def _check_raw_compiler_params(ctx: FileCtx) -> Iterator[Finding]:
    if ctx.in_tree(_SHIM_FILE):
        return
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and _callee_name(node) in ("TPUCompilerParams",
                                           "CompilerParams")):
            yield from ctx.finding(
                "raw-compiler-params", node,
                f"direct {_callee_name(node)}(...) construction; use "
                "repro.kernels.tpu_compiler_params instead")


# Quant-range literals. Positive 128 alone is *not* banned (it is the
# ubiquitous MXU tile / block size); the banned spellings are the clip
# bounds -128, -127, 127 and the int4 denominator 15.
_BANNED_POS = {127, 127.0, 15, 15.0}     # quantlint: disable=magic-quant-literal
_BANNED_NEG = {127, 127.0, 128, 128.0}   # quantlint: disable=magic-quant-literal


@rule("magic-quant-literal",
      "quant-range literals (-128/-127/127/15) must come from "
      "qtypes.qmin(bits)/qmax(bits)")
def _check_magic_literal(ctx: FileCtx) -> Iterator[Finding]:
    if ctx.in_tree(_QTYPES_FILE):
        return
    negated = set()
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub)
                and isinstance(node.operand, ast.Constant)
                and type(node.operand.value) in (int, float)
                and node.operand.value in _BANNED_NEG):
            negated.add(node.operand)
            yield from ctx.finding(
                "magic-quant-literal", node,
                f"magic quant-range literal -{node.operand.value!r}; use "
                "qtypes.qmin(bits)")
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Constant) and node not in negated
                and type(node.value) in (int, float)
                and node.value in _BANNED_POS):
            yield from ctx.finding(
                "magic-quant-literal", node,
                f"magic quant-range literal {node.value!r}; use "
                "qtypes.qmax(bits) (or 2**bits - 1 via qtypes helpers)")


@rule("no-float64", "no float64 dtypes anywhere in the pipeline")
def _check_float64(ctx: FileCtx) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64":  # quantlint: disable=no-float64
            yield from ctx.finding(
                "no-float64", node, "float64 dtype (pipeline is "
                "f32/bf16/int; f64 silently disables TPU fast paths)")
        elif isinstance(node, ast.Constant) and node.value == "float64":  # quantlint: disable=no-float64
            yield from ctx.finding(
                "no-float64", node, 'dtype string "float64"')


@rule("pallas-interpret",
      "kernel wrappers must plumb an interpret= escape hatch into "
      "pallas_call")
def _check_interpret(ctx: FileCtx) -> Iterator[Finding]:
    if "/kernels/" not in ctx.rel and not ctx.rel.startswith("kernels/"):
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _callee_name(node) == "pallas_call"):
            continue
        if _kw(node, "interpret") is None:
            yield from ctx.finding(
                "pallas-interpret", node,
                "pallas_call without interpret= (kernels must keep a CPU "
                "interpret-mode escape hatch)")
            continue
        funcs = ctx.enclosing_functions(node)
        has_param = any(
            any(a.arg == "interpret"
                for a in (f.args.args + f.args.kwonlyargs))
            for f in funcs)
        if not has_param:
            yield from ctx.finding(
                "pallas-interpret", node,
                "enclosing kernel wrapper does not expose an interpret= "
                "parameter (escape hatch must reach callers)")


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def lint_file(path: Path, *, rel: Optional[str] = None,
              rules: Optional[Iterable[str]] = None) -> List[Finding]:
    source = path.read_text()
    try:
        ctx = FileCtx(path, source, rel=rel)
    except SyntaxError as e:
        return [Finding(rel or str(path), e.lineno or 0, "parse-error",
                        f"could not parse: {e.msg}")]
    active = [RULES[r] for r in rules] if rules else list(RULES.values())
    out: List[Finding] = []
    for r in active:
        out.extend(r.check(ctx))
    return out


def iter_python_files(paths: Iterable[str]) -> Iterator[Path]:
    for p in paths:
        pp = Path(p)
        if pp.is_dir():
            yield from sorted(pp.rglob("*.py"))
        elif pp.suffix == ".py":
            yield pp


def lint_paths(paths: Iterable[str],
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for f in iter_python_files(paths):
        out.extend(lint_file(f, rel=str(f), rules=rules))
    return out
