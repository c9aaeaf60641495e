"""Mean wait of a write in its document's queue, from admission (after
its parse) to the drain of the scheduler round that takes it, over
every write drained in the window (``serve.queue_wait`` in the span
registry)."""


def read(ctx):
    s = ctx.spans.get("serve.queue_wait")
    return s["total_ms"] / s["count"] if s else None
