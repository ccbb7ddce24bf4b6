"""90th percentile of the scheduler's wait, from the engine's submit to the
request's first admission into a lane, over the requests due in the window
(engine request records); one not admitted when the loop stopped counts
with its wait until then. Requests held by the profiler are left out."""
from bench.engine_records import window_requests
from bench.stats import percentile


def read(ctx):
    stop = ctx.record.stop
    waits = [min(v.t_admit if v.t_admit is not None else stop, stop)
             - v.t_submit for v in window_requests(ctx) or []]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
