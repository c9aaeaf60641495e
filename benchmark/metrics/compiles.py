"""JAX lowerings inside the window: every new jit specialisation,
whether the persistent cache held it or not.  Should read 0."""


def read(ctx):
    return ctx.compiles
