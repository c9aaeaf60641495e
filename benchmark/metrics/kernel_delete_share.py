"""Share of the window's single-document kernel merge rows that are
Deletes the kernel applied, in %: ``100 x kernel_deletes_applied /
kernel_rows`` (engine counters; rows of the padded buckets).  Nothing
where the program does not count them."""


def read(ctx):
    rows = ctx.counters.get("kernel_rows", 0)
    if not rows or "kernel_deletes_applied" not in ctx.counters:
        return None
    return 100.0 * ctx.counters["kernel_deletes_applied"] / rows
