"""Host time handing each candidate set to the device and waiting for
its statuses, per commit resolved in the window: ``engine.launch``
(dispatch and host-to-device copy) plus ``engine.sync`` (the status
readback, which waits for the kernel)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _per_commit import span_ms_per_commit  # noqa: E402


def read(ctx):
    return span_ms_per_commit(ctx, "engine.launch", "engine.sync")
