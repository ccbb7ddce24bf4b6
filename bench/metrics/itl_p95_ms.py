"""95th percentile of the gaps between consecutive output tokens of one
request, over all gaps of all requests that end in the window."""
from bench.stats import gaps_in, percentile


def read(ctx):
    lo, hi = ctx.record.window
    gaps = [g for r in ctx.record.requests for g in gaps_in(r.tokens, lo, hi)]
    p = percentile(gaps, 95)
    return None if p is None else 1e3 * p
