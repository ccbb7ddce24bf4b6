"""Host time per decode step: the host clock around the window's engine
steps that ran the decode program, in total, over their number."""


def read(ctx):
    steps = ctx.record.window_steps("decode")
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
