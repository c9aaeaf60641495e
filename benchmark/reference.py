"""The plain reference: what a CRDTree document must read back as, from
its ops alone.  Imports nothing of the program.

Semantics (the reference ``CRDTree``, maca/crdt-graph, as documented in
this repo's ``ops/merge.py`` and ``core/node.py``):

- ``("add", parent_path + (anchor,), ts, val)`` inserts node ``ts`` in
  the branch ``parent_path`` after sibling ``anchor`` (0 = the branch
  head); a second add of the same ``ts`` in the branch is absorbed;
- ``("del", parent_path + (ts,))`` tombstones that node: it keeps its
  place among its siblings, and it and its branch are not visible;
- within a branch the converged order is the greedy max-timestamp
  linearisation of the anchor forest: repeatedly emit the
  largest-timestamp node whose anchor has been emitted;
- the document order is a walk: a node, then its own branch, then the
  siblings after it.

:func:`visible_values` computes it with a heap per branch.  The control
(:func:`control_values`) is the same walk with the tie-break reversed
(smallest timestamp first): it breaks the one guarantee the
configurations state about order, convergence to the RGA order.
"""
from __future__ import annotations

import heapq
import json
import re
from collections import Counter, defaultdict
from typing import Iterable, List, Sequence

import numpy as np


def _index(ops: Iterable[tuple]):
    nodes = {}                        # full path -> [val, deleted]
    after = defaultdict(list)         # (branch, anchor ts) -> [ts]
    dels = []
    for op in ops:
        if op[0] == "add":
            _, path, ts, val = op
            branch = tuple(path[:-1])
            key = branch + (ts,)
            if key in nodes:
                continue
            nodes[key] = [val, False]
            after[(branch, path[-1])].append(ts)
        else:
            dels.append(tuple(op[1]))
    for path in dels:
        if path in nodes:
            nodes[path][1] = True
    return nodes, after


def _walk(ops: Iterable[tuple], sign: int) -> list:
    nodes, after = _index(ops)
    out: list = []

    def branch(parent: tuple) -> None:
        heap = [sign * t for t in after.get((parent, 0), ())]
        heapq.heapify(heap)
        while heap:
            ts = sign * heapq.heappop(heap)
            key = parent + (ts,)
            val, dead = nodes[key]
            if not dead:
                out.append(val)
                branch(key)
            for t in after.get((parent, ts), ()):
                heapq.heappush(heap, sign * t)

    branch(())
    return out


def visible_values(ops: Iterable[tuple]) -> list:
    """Visible values in document order (the reference)."""
    return _walk(ops, -1)


def control_values(ops: Iterable[tuple]) -> list:
    """The control: the reference with the RGA tie-break reversed."""
    return _walk(ops, 1)


def chain_control_values(cols: dict, per: int) -> list:
    """The control for a chain log: chains in ASCENDING replica order."""
    rid = cols["ts"] // 2**32
    heads = np.flatnonzero(np.arange(rid.shape[0]) % per == 0)
    by_rid = heads[np.argsort(rid[heads], kind="stable")]
    idx = (by_rid[:, None] + np.arange(per)[None, :]).ravel()
    return cols["val"][idx].tolist()


# -- the comparisons --------------------------------------------------------

def value_mismatches(got: Sequence, want: Sequence) -> int:
    """Positions at which two value sequences differ, plus the
    difference in length."""
    n = min(len(got), len(want))
    a = np.asarray(got[:n], dtype=object)
    b = np.asarray(want[:n], dtype=object)
    return int(np.count_nonzero(a != b)) + abs(len(got) - len(want))


_ADD1 = re.compile(rb'\{"op":"add","path":\[(\d+)\],"ts":(\d+),"val":(\d+)\}')


def opset_mismatches_chain(body: bytes, cols: dict) -> int:
    """Ops served by ``GET /docs/{id}/ops?since=0`` against a chain
    log's columns: rows present on one side only, counted both ways.
    Any op that is not a depth-1 add counts as a mismatch."""
    m = np.array(_ADD1.findall(body), dtype=np.int64).reshape(-1, 3)
    total = body.count(b'{"op":')  - body.count(b'{"op":"batch"')
    extra = total - m.shape[0]
    got = m[np.argsort(m[:, 1], kind="stable")]
    want = np.stack([cols["anchor"], cols["ts"], cols["val"]], axis=1)
    want = want[np.argsort(want[:, 1], kind="stable")]
    if got.shape != want.shape:
        return extra + _set_diff(got, want)
    return extra + 2 * int(np.count_nonzero((got != want).any(axis=1)))


def _set_diff(a: np.ndarray, b: np.ndarray) -> int:
    ca = Counter(map(tuple, a.tolist()))
    cb = Counter(map(tuple, b.tolist()))
    return sum(((ca - cb) + (cb - ca)).values())


def _canon(obj) -> tuple:
    if obj.get("op") == "add":
        return ("add", tuple(obj["path"]), obj["ts"], obj["val"])
    return (obj.get("op"), tuple(obj.get("path", ())))


def _leaves(obj) -> List[dict]:
    if obj.get("op") == "batch":
        out = []
        for o in obj.get("ops", ()):
            out.extend(_leaves(o))
        return out
    return [obj]


def opset_mismatches(body: bytes, ops: Iterable[tuple]) -> int:
    """Ops served by ``GET /docs/{id}/ops?since=0`` against the ops
    acked to the document, as multisets: ops on one side only."""
    got = Counter(_canon(o) for o in _leaves(json.loads(body)))
    want = Counter((op[0], tuple(op[1]), op[2], op[3]) if op[0] == "add"
                   else (op[0], tuple(op[1])) for op in ops)
    return sum(((got - want) + (want - got)).values())
