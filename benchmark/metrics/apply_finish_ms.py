"""Host time committing each merged chunk, per commit resolved in the
window: ``engine.finish`` (status check, replica clocks, log extension,
view bookkeeping)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _per_commit import span_ms_per_commit  # noqa: E402


def read(ctx):
    return span_ms_per_commit(ctx, "engine.finish")
