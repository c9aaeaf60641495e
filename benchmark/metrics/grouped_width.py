"""Mean documents per cross-document grouped launch in the window
(scheduler counters ``cross_doc_docs`` / ``cross_doc_batches``)."""


def read(ctx):
    n = ctx.counters.get("cross_doc_batches", 0)
    return ctx.counters.get("cross_doc_docs", 0) / n if n else None
