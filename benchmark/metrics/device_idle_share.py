"""Share of the window in which no operation ran on the device, in %
(profiler trace; busy = union of the operation intervals, averaged
over the chips)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_ns"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_ns"] / ctx.trace["window_ns"])
