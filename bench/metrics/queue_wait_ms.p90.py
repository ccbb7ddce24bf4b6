"""90th percentile of the wait from due time to admission into a lane
(seen in the scheduler's active lanes after each step) of the requests due
in the window; one not admitted when the loop stopped counts with its wait
until then. Requests that waited across the profiler's start or stop in a
traced run are left out, as for gen_lag_ms.p95."""
from bench.stats import percentile


def read(ctx):
    rec = ctx.record
    waits = [(r.admit if r.admit is not None else rec.stop) - r.due
             for r in rec.due_in_window() if not rec.held_by_profiler(r)]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
