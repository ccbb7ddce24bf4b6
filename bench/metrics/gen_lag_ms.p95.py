"""95th percentile of how late the load generator submitted the requests
due in the window: submit time minus due time. Requests that waited across
the profiler's start or stop in a traced run are left out: that stall is
the instrumentation's (PERF.md gives its length)."""
from bench.stats import percentile


def read(ctx):
    rec = ctx.record
    lags = [r.submit - r.due for r in rec.due_in_window()
            if not rec.held_by_profiler(r)]
    p = percentile(lags, 95)
    return None if p is None else 1e3 * p
