"""50th percentile of time to first token over all requests due in the
window, each from its due time; a request with no first token when the loop
stopped counts with its wait until then."""
from bench.stats import percentile, ttfts


def read(ctx):
    reqs = ctx.record.due_in_window()
    firsts = [r.tokens[0] if r.tokens else None for r in reqs]
    p = percentile(ttfts([r.due for r in reqs], firsts, ctx.record.stop),
                   50)
    return None if p is None else 1e3 * p
