"""Share of the traced steps' span in which no op ran on the device:
1 - (union of device-op intervals) / (first step's start to last step's
end), from bench/trace.py."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
