"""Operations and bytes that a kernel call or a model step needs, computed
from shapes and the lanes' real lengths, whatever implements them.

Each kernel has a module of its own here, `bench/counts/<kernel>.py`, with
`calls(arch, model, quant, step)`: the (ops, bytes) of each call that one
engine step makes of that kernel. `arch` is the configuration's
architecture (`bench/arch/<arch>/`, as `bench.run.load_arch` loads it),
which gives the shapes the formulas need (its linears, its cache bytes,
its attention operations per key, its layers); `step` is a
`bench.drive.StepRecord`: its kind ("mixed" or "decode") and its working
lanes (q_start, n_new). A kernel's least time at the chip's peaks is the
sum over its calls of max(ops / peak rate, bytes / bandwidth), which is
never more than the time the calls took, so a share of it cannot pass 100%
on correct counts.
"""
from __future__ import annotations

import importlib
from typing import Iterable, Tuple


def load(kernel: str):
    return importlib.import_module(f"bench.counts.{kernel}")


def least_time(calls: Iterable[Tuple[float, float]], op_rate: float,
               bandwidth: float) -> float:
    """Seconds at the peaks for these (ops, bytes) calls."""
    return sum(max(ops / op_rate, nbytes / bandwidth) for ops, nbytes in calls)


def roofline_share(ctx, kernel: str, op_rate: str):
    """Percent of `kernel`'s summed device time in the traced slice that
    its calls in the traced steps need at the peaks (`op_rate` names the
    peak its operations run at); None where the trace holds no such call."""
    if not ctx.trace:
        return None
    spent = ctx.trace["kernel_s"].get(kernel, 0.0)
    mod = load(kernel)
    calls = [c for s in ctx.record.steps if s.traced
             for c in mod.calls(ctx.arch, ctx.model, ctx.quant, s)]
    if spent <= 0 or not calls:
        return None
    need = least_time(calls, ctx.peaks[op_rate], ctx.peaks["hbm_bw"])
    return 100.0 * need / spent


def context_keys(q_start: int, n_new: int) -> int:
    """Keys the queries q_start .. q_start + n_new - 1 attend to, causally."""
    return n_new * q_start + n_new * (n_new + 1) // 2
