"""Host time building the kernel's candidate sets, per commit resolved
in the window: ``engine.prepare`` (the union of the log with each
chunk, and its slot-hint derivation)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _per_commit import span_ms_per_commit  # noqa: E402


def read(ctx):
    return span_ms_per_commit(ctx, "engine.prepare")
