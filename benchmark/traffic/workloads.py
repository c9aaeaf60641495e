"""Document logs and editor deltas, generated from a seed.

Copied from ``crdt_graph_tpu/bench/workloads.py`` (``chain_workload``,
``chain_expected_ts``, ``nested_tree``) and ``bench/loadgen.py`` (the
editor delta), so that a later change there cannot move the yardstick.
Departures from the originals, each needed for a seeded, checkable run:

- replica ids are drawn from the seed instead of ``1..n``: distinct,
  in ``[10**18 / 2**32, 2**30)`` so that every timestamp has 19 digits
  (every seed sends the same bytes), and never the ids ``1..`` that
  the server hands out through ``POST /docs/{id}/replicas``;
- every op's value is distinct (its index in the log), so that a read
  of the document shows the order and not only the count;
- ops are plain tuples (``("add", path, ts, val)``, ``("del", path)``),
  not the program's ``Operation`` objects.

A log is returned as a :class:`DocLog`: its wire body, its ops, and for
the chain its columns and the closed-form visible order.
"""
from __future__ import annotations

import dataclasses
import random
from typing import List, Optional, Tuple

import numpy as np

from . import wire

OFFSET = 2**32
# every timestamp has 19 digits, so every seed sends the same bytes
RID_LO, RID_HI = 10**18 // 2**32 + 1, 2**30


@dataclasses.dataclass
class DocLog:
    body: bytes                       # POST /docs/{id}/ops wire body
    n_ops: int
    ops: Optional[list] = None        # op tuples (nested logs)
    cols: Optional[dict] = None       # anchor/ts/val columns (chain)
    expected: Optional[list] = None   # closed-form visible values
    branch_paths: Tuple[tuple, ...] = ((),)


def replica_ids(rng: random.Random, n: int) -> List[int]:
    """``n`` distinct replica ids drawn from the seed."""
    return rng.sample(range(RID_LO, RID_HI), n)


def chain_log(n_replicas: int, n_ops: int, rng: random.Random) -> DocLog:
    """``chain_workload``: ``n_replicas`` flat insertion chains of
    ``n_ops / n_replicas`` ops each, every chain starting at the branch
    head, so the merge interleaves them under the RGA rule.  The seed
    picks the replica ids; the chains are listed in ascending id order,
    as ``chain_workload`` lists them.  (A listing order drawn from the
    seed changed the host's work: two runs of one seed agreed within
    5%, two seeds differed by up to 14%.)"""
    per = n_ops // n_replicas
    rids = np.array(sorted(replica_ids(rng, n_replicas)), dtype=np.int64)
    rid = np.repeat(rids, per)
    counter = np.tile(np.arange(1, per + 1, dtype=np.int64), n_replicas)
    ts = rid * OFFSET + counter
    anchor = np.where(counter == 1, 0, ts - 1)
    val = np.arange(rid.shape[0], dtype=np.int64)
    cols = {"anchor": anchor, "ts": ts, "val": val}
    return DocLog(body=wire.dumps_chain(anchor, ts, val),
                  n_ops=int(ts.shape[0]), cols=cols,
                  expected=chain_expected_values(cols, per))


def chain_expected_values(cols: dict, per: int) -> list:
    """``chain_expected_ts`` as values: the converged order is the
    replicas in DESCENDING id order, each replica's ops in counter
    order (the greedy max-timestamp linearisation of chains that all
    start at the branch head)."""
    rid = cols["ts"] // OFFSET
    heads = np.flatnonzero(np.arange(rid.shape[0]) % per == 0)
    by_rid = heads[np.argsort(-rid[heads], kind="stable")]
    idx = (by_rid[:, None] + np.arange(per)[None, :]).ravel()
    return cols["val"][idx].tolist()


def nested_log(n_ops: int, n_replicas: int, depth: int, burst: int,
               rng: random.Random) -> DocLog:
    """``nested_tree``: a depth-``depth`` nested tree, add-dominated.
    One replica builds a skeleton of nested branches; then replicas
    append bursts of ``burst`` chars under branches at every level,
    each burst anchored at the branch head and then at its own
    previous char (causally valid without cross-replica anchors)."""
    rids = replica_ids(rng, n_replicas)
    counters = [0] * n_replicas
    ops: list = []

    def stamp(r):
        counters[r] += 1
        return rids[r] * OFFSET + counters[r]

    branch_paths = [()]
    path: tuple = ()
    for _ in range(depth - 1):
        ts = stamp(0)
        ops.append(("add", path + (0,), ts, len(ops)))
        path = path + (ts,)
        branch_paths.append(path)
    remaining = n_ops - len(ops)
    while remaining > 0:
        r = rng.randrange(n_replicas)
        parent = rng.choice(branch_paths)
        anchor = 0
        for _ in range(min(burst, remaining)):
            ts = stamp(r)
            ops.append(("add", parent + (anchor,), ts, len(ops)))
            anchor = ts
        remaining -= burst
    return DocLog(body=wire.dumps_ops(ops), n_ops=len(ops), ops=ops,
                  branch_paths=tuple(branch_paths))


class EditorSession:
    """The editor delta of ``bench/loadgen.py``: appends at the caret
    (the session's own last char in its branch), with backspaces of
    its own last char at ``backspace_p``.  ``rid`` is the replica id
    the server assigned; ``tag`` makes every value distinct."""

    def __init__(self, rid: int, branch: tuple, backspace_p: float,
                 rng: random.Random, tag: int):
        self.rid = rid
        self.branch = branch
        self.backspace_p = backspace_p
        self.rng = rng
        self.tag = tag
        self.counter = 0
        self.alive: List[int] = []
        self.last_add_ts = 0

    def delta(self, size: int) -> list:
        ops = []
        for _ in range(size):
            if self.alive and self.rng.random() < self.backspace_p:
                ops.append(("del", self.branch + (self.alive.pop(),)))
            else:
                self.counter += 1
                ts = self.rid * OFFSET + self.counter
                anchor = self.alive[-1] if self.alive else 0
                ops.append(("add", self.branch + (anchor,), ts,
                            self.tag * OFFSET + self.counter))
                self.alive.append(ts)
                self.last_add_ts = ts
        return ops

    def undo(self, ops: list) -> None:
        """Forget a delta the server did not ack (its adds leave the
        caret; its backspaces are not redone)."""
        added = {op[2] for op in ops if op[0] == "add"}
        self.alive = [t for t in self.alive if t not in added]
        self.last_add_ts = self.alive[-1] if self.alive else 0
