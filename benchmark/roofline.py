"""The work of a merge, from shapes alone, and the chip's peaks.

:func:`merge_bytes` is the least HBM traffic of merging ``n_ops`` ops
whose paths hold ``depth`` timestamps, whatever implements it: read
each op's input columns once and write its node-table row once, with
the column types of the wire's packed form (``kind`` i8; ``ts``,
``parent_ts``, ``anchor_ts`` i64; ``depth`` i32; ``paths`` i64 x
depth; ``value_ref`` i32) and of the node table (``ts`` i64;
``parent``, ``depth``, ``value_ref`` i32; ``paths`` i64 x depth;
``exists``, ``tombstone``, ``dead``, ``visible`` bool; ``doc_index``,
``order``, ``visible_order`` i32).
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

IN_BYTES = 1 + 8 + 8 + 8 + 4 + 4          # + 8 per path element
OUT_BYTES = 8 + 4 + 4 + 4 + 4 * 1 + 4 * 3   # + 8 per path element


def merge_bytes(n_ops: int, depth: int) -> int:
    return n_ops * (IN_BYTES + OUT_BYTES + 16 * depth)


def peak(device_kind: str) -> dict:
    """The peaks of one chip; a device not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]
