"""Share of the window's single-document kernel merge rows that the
route sharded over the ops axis, in %: ``100 x opsaxis_rows /
kernel_rows`` (engine counters, bucket rows).  Nothing where the
program does not count them."""


def read(ctx):
    rows = ctx.counters.get("kernel_rows", 0)
    if not rows:
        return None
    return 100.0 * ctx.counters.get("opsaxis_rows", 0) / rows
