"""Device time of the merge executables in the window, per write
acked in it (profiler trace)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _merge import merge_ns  # noqa: E402


def read(ctx):
    n = len(ctx.acked_writes)
    if ctx.trace is None or not n:
        return None
    ns = merge_ns(ctx.trace)
    return ns / 1e6 / n if ns else None
