"""Host time per engine step: over the window's working steps, the mean of
each `serve.step` span less its `serve.wait` child (the read-back of the
sampled tokens, which blocks on the device)."""
from bench.engine_records import window_steps


def read(ctx):
    steps = window_steps(ctx)
    if not steps:
        return None
    return 1e3 * sum(total - wait for _, total, wait in steps) / len(steps)
