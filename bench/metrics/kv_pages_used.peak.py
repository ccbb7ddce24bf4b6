"""The most KV pool pages held by requests (`alloc.n_live`, parked prefix
cache pages not counted) after any working step of the window, as a share
of the pool's pages."""
from bench.engine_records import window_steps


def read(ctx):
    steps = window_steps(ctx)
    if not steps:
        return None
    return 100.0 * max(r.pages_live for r, _, _ in steps) / \
        ctx.engine["n_pages"]
