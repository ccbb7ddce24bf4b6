"""Useful model work of the window's decode steps at the chip's peak rates
(bench/counts/step.py: int8 operations of the linears at the int8 peak,
attention and the LM head at the bf16 peak), over their host-clock time."""
from bench.counts import step


def read(ctx):
    steps = ctx.record.window_steps("decode")
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    need = sum(step.least_time(ctx.arch, ctx.model, s, ctx.peaks)
               for s in steps)
    return 100.0 * need / wall
