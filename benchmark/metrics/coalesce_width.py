"""Mean tickets per fused commit in the window (scheduler counters
``fused_tickets`` / ``fused_batches``; a fused commit carries two or
more writes to one document)."""


def read(ctx):
    n = ctx.counters.get("fused_batches", 0)
    return ctx.counters.get("fused_tickets", 0) / n if n else None
