"""What the serving engine recorded about itself (`engine.telemetry`,
src/repro/serving/telemetry.py), joined to the benchmark's window and
requests for the metric readers that read it.

A program that has no such recorder gives None here, and those readers then
return None: the metric is left out of the result line.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def recorder():
    """The newest live engine's recorder, or None."""
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    return telemetry.current()


def window_steps(ctx, kind: Optional[str] = None) -> Optional[List[Tuple]]:
    """(step record, `serve.step` seconds, `serve.wait` seconds) of each
    working step (of `kind`, if given) whose `serve.step` span lies inside
    the window; None without a recorder."""
    rec = recorder()
    if rec is None:
        return None
    lo, hi = ctx.record.window
    return [(r, sp.t1 - sp.t0, wait) for r, sp, wait in rec.step_times()
            if lo <= sp.t0 and sp.t1 <= hi and r.kind != "idle"
            and (kind is None or r.kind == kind)]


def window_requests(ctx) -> Optional[List]:
    """The engine's view of each request due in the window, joined by rid.
    Those that waited across the profiler's start or stop are left out:
    before submission, as in queue_wait_ms.p90, or inside the engine
    between submission and first token (until the loop stopped, for one
    with none). None without a recorder."""
    rec = recorder()
    if rec is None:
        return None
    record = ctx.record
    views = []
    for r in record.due_in_window():
        if record.held_by_profiler(r):
            continue
        try:
            v = rec.request(r.rid)
        except KeyError:             # finished and gone from the ring
            continue
        end = record.stop if v.t_first is None else v.t_first
        if any(v.t_submit < b and end > a for a, b in record.stalls):
            continue
        views.append(v)
    return views
