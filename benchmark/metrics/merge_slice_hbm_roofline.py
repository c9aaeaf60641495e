"""The merge's share of the slice's HBM roofline, in %: the least bytes
any merge of the window's acked ops must move (``roofline.py``) over
the bandwidth of every chip of the cell (``peaks.json`` times
``chips``), divided by the device time of the merge executables in the
window (averaged over the chips).  The same work, whatever implements
it, over the slice's bandwidth: on four chips a merge sharded over the
ops axis can use four chips' HBM."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
import roofline  # noqa: E402
from _merge import merge_ns  # noqa: E402


def read(ctx):
    if ctx.trace is None:
        return None
    ns = merge_ns(ctx.trace)
    if not ns:
        return None
    depth = ctx.config.get("depth", 1)
    nbytes = sum(roofline.merge_bytes(r.n_ops, depth)
                 for r in ctx.acked_writes)
    bw = ctx.cell["chips"] * \
        roofline.peak(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / bw) / (ns / 1e9)
