"""Ops acknowledged per second: every op of every write acked in the
window, over the window's seconds (from its start until the last
request issued in it was answered)."""


def read(ctx):
    return ctx.acked_ops / ctx.window_s
