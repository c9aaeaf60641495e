"""Host time placing the op columns of sharded merges on the ops-axis
mesh, per commit resolved in the window: ``opsaxis.place`` (inside
``engine.launch``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _per_commit import span_ms_per_commit  # noqa: E402


def read(ctx):
    return span_ms_per_commit(ctx, "opsaxis.place")
