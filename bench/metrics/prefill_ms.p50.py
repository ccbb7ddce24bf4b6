"""50th percentile of prefill time, from a request's first admission to the
end of the device wait that gave its first token, over the admitted
requests due in the window (engine request records); one with no first
token when the loop stopped counts with its time until then. Requests held
by the profiler are left out."""
from bench.engine_records import window_requests
from bench.stats import percentile


def read(ctx):
    stop = ctx.record.stop
    times = [min(v.t_first if v.t_first is not None else stop, stop)
             - v.t_admit for v in window_requests(ctx) or []
             if v.t_admit is not None]
    p = percentile(times, 50)
    return None if p is None else 1e3 * p
