"""Device wait per decode step: the mean `serve.wait` span (the read-back
of the sampled tokens, which blocks until the step program is done) of the
window's steps that ran the decode program."""
from bench.engine_records import window_steps


def read(ctx):
    steps = window_steps(ctx, "decode")
    if not steps:
        return None
    return 1e3 * sum(wait for _, _, wait in steps) / len(steps)
