"""Mean host commit time per commit resolved in the window: the
``fuse``, ``merge``, ``batch_prepare``, ``batched_launch`` and
``publish`` stages of its flight record."""

STAGES = ("fuse", "merge", "batch_prepare", "batched_launch", "publish")


def read(ctx):
    recs = [r for r in ctx.commits if r["outcome"] == "committed"]
    if not recs:
        return None
    return sum(sum(r["stages_ms"].get(s, 0.0) for s in STAGES)
               for r in recs) / len(recs)
