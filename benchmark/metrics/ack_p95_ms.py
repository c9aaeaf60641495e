"""95th percentile of push-to-ack latency over every write acked in the
window, timed at the client from when the write was due."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _pct import pct  # noqa: E402


def read(ctx):
    return pct([(r.t_done - r.t_due) * 1e3 for r in ctx.acked_writes], 95)
