"""Seconds from the process's start to the window's: JAX and device
start-up, logs made from the seed, the server, the warm-up shapes (and
their compiles, where the cache misses)."""


def read(ctx):
    return ctx.setup_s
