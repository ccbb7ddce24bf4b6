"""Process start to the window's start: imports, weights and their
quantization, compiling or loading the programs, warm-up, and the set-up the
traffic needs (warm load, or the sessions' caches)."""


def read(ctx):
    return ctx.setup_s
