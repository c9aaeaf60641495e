"""Span time per commit resolved in the window: the total of the named
spans in the span registry over the committed flight records."""


def span_ms_per_commit(ctx, *names):
    got = [ctx.spans[n]["total_ms"] for n in names if n in ctx.spans]
    n = sum(1 for r in ctx.commits if r["outcome"] == "committed")
    return sum(got) / n if got and n else None
