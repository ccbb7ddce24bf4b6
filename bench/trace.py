"""Reduction of a profiler trace to the numbers the per-layer metrics read.

`load(directory)` reads the `.xplane.pb` that `jax.profiler` wrote and keeps
what the reduction needs: the device ops of each chip ("XLA Ops" lines of
the `/device:TPU:n` planes) and the benchmark's own host spans, as
[name, start_ns, duration_ns] on the trace's one clock. `reduce(...)` turns
that into:

  busy_s, window_s  union of the intervals in which an op ran on a chip
                    (averaged over chips), and the length of the traced
                    steps, from the first `engine.step` span's start to the
                    last one's end
  kernel_s          summed device time of the ops of each name, with the
                    numeric suffix taken off (`%int8_matmul.34 = ...` counts
                    under "int8_matmul"), so a kernel is found by its name
  device_ops        the ops that took most time, by name, container ops
                    (while, conditional, call) left out
  idle_gaps         the longest gaps with no op on the device, each named by
                    the host span that covers its middle
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List

HOST_SPANS = ("engine.step", "traffic.submit", "bench.observe", "bench.idle")
_OP_NAME = re.compile(r"^%?([\w.\-]+)")
_CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")
TOP = 10


def op_name(event_name: str) -> str:
    """'%int8_matmul.34 = f32[...] custom-call(...)' -> 'int8_matmul.34'."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def kernel_of(name: str) -> str:
    """'int8_matmul.34' -> 'int8_matmul'."""
    return re.sub(r"\.\d+$", "", name)


def load(directory: str) -> dict:
    import jax
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events if ev.name in HOST_SPANS]
    return {"devices": devices, "host": host}


def _union(intervals: Iterable[tuple]) -> List[tuple]:
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(x) for x in merged]


def reduce(trace: dict) -> dict:
    steps = [(s, s + d) for n, s, d in trace["host"] if n == "engine.step"]
    if not steps or not any(trace["devices"]):
        return {}
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    window_ns = hi - lo
    busy, kernel_ns, per_op = [], {}, {}
    gaps = []
    for ops in trace["devices"]:
        inside = [(op_name(n), s, d, n) for n, s, d in ops
                  if s < hi and s + d > lo]
        spans = _union((max(s, lo), min(s + d, hi)) for _, s, d, _ in inside)
        busy.append(sum(b - a for a, b in spans))
        for name, s, d, full in inside:
            k = kernel_of(name)
            kernel_ns[k] = kernel_ns.get(k, 0.0) + d
            if not _CONTAINER.search(full):
                per_op[name] = per_op.get(name, 0.0) + d
        edges = [lo] + [x for ab in spans for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = sorted((s, s + d, n) for n, s, d in trace["host"])

    def label(a, b):
        mid = (a + b) / 2
        covering = [(e - s, n) for s, e, n in host if s <= mid <= e]
        return min(covering)[1] if covering else "host.other"

    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = len(trace["devices"])
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, v / n_dev / 1e9] for n, v in
                       sorted(per_op.items(), key=lambda x: -x[1])[:TOP]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:TOP]],
    }
