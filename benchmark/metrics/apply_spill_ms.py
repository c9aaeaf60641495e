"""Host time the op log spends spilling its hot tail to disk at a
commit's boundary, per commit resolved in the window: ``engine.spill``
(after the last chunk of a chunked apply, which defers it)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _per_commit import span_ms_per_commit  # noqa: E402


def read(ctx):
    return span_ms_per_commit(ctx, "engine.spill")
