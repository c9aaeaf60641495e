"""The TPU replica engine: an array-backed CRDTree.

``TpuTree`` keeps the replica state the semilattice way: the state IS the
operation set, and the tree is a materialised view.  TWO materialisation
paths share that state, split by delta size:

- **Batched kernel** (ops/merge.py) for large deltas — anti-entropy
  catch-up, bulk merges, the path BASELINE.json targets: O(n log n) work at
  O(log n) parallel depth instead of the reference's sequential per-op fold
  (CRDTree.elm:224-232, 408-418).
- **Host mirror** (host_tree.py) for small deltas and ALL interactive
  reads: the reference's own O(depth·log b + siblings) per-op application
  (Internal/Node.elm:51-104) on mutable slot arrays, so a 1-op remote
  delta on an n-op document costs O(delta), not a full re-merge.  After a
  kernel merge the mirror is rebuilt from the NodeTable in one vectorised
  pass, on the first host-path edit or node-API read only (the visible
  sequence reads straight from the table); host applies in turn mark the
  device view stale.

API parity: method names and semantics mirror the oracle ``CRDTree``
(core/tree.py) — local edits stamp ``replica_id * 2**32 + counter``
timestamps and move the cursor, remote ``apply`` does not move the cursor,
``operations_since`` serves pull-based anti-entropy from the vector clock,
idempotent redelivery is absorbed, and failing remote batches raise without
mutating state (host path: sequential apply + undo journal; kernel path:
materialise-then-commit).  Unlike the persistent oracle, ``TpuTree`` is a
MUTABLE container (it's the server-side engine; snapshot with
``checkpoint``/``restore``).

View lifetimes: mirror slots are append-only, so ``TableNode`` views stay
valid across host-path edits; a kernel merge compacts slots and bumps the
generation, so views crossing it fail loudly (StaleNodeView).
"""
from __future__ import annotations

import os
import threading
import weakref
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, \
    Tuple

import numpy as np

from .codec import packed as packed_mod
from .codec.packed import DEFAULT_MAX_DEPTH, PackedOps
from .core import operation as op_mod
from .core import timestamp as ts_mod
from .core.errors import InvalidPathError, NotFound, OperationFailedError
from .core.operation import Add, Batch, Delete, Operation
from .host_tree import NIL, HostTree
from .oplog import OpLog, PackedBatch
from .ops import merge as merge_mod
from .ops import view as view_mod
from .ops.merge import APPLIED, INVALID_PATH, NOT_FOUND, NodeTable
from .utils import profiling

# deltas at or under this many leaves apply host-side in O(delta); larger
# ones re-materialise through the batched kernel
DELTA_THRESHOLD = 256


# the materialized view's and the kernel commit's process-wide tally,
# shown among every serving engine's counters (serve/engine.py;
# docs/OBSERVABILITY.md)
_VIEW_STATS = {"visible_from_table": 0, "mirror_builds": 0,
               "kernel_deletes_applied": 0}
_VIEW_STATS_LOCK = threading.Lock()


def _count_view(name: str, n: int = 1) -> None:
    with _VIEW_STATS_LOCK:
        _VIEW_STATS[name] += n


def view_counts() -> Dict[str, int]:
    """``visible_from_table``: visible sequences read straight from a
    kernel table; ``mirror_builds``: host mirror (re)builds;
    ``kernel_deletes_applied``: Delete rows a committed kernel merge
    applied (:meth:`TpuTree.finish_packed`)."""
    with _VIEW_STATS_LOCK:
        return dict(_VIEW_STATS)


# Device tables a visible read left parked, by id: each has its host copy
# in flight, and the next kernel merge in this process swaps it into its
# document before it launches, so a document nobody touches again holds
# no device memory.  Every swap of a tree's ``_table`` takes
# ``_PARK_LOCK``: the swap may run on another thread than the tree's
# writer, and must never put back a table a newer commit replaced.
_PUBLISHED: Dict[int, Tuple["weakref.ref[TpuTree]", NodeTable]] = {}
_PARK_LOCK = threading.Lock()


def _publish_device_table(tree: "TpuTree", table: NodeTable) -> None:
    """Start ``table``'s host copy and queue its swap into ``tree``."""
    import jax
    if id(table) in _PUBLISHED:
        return
    for col in jax.tree.leaves(table):
        col.copy_to_host_async()
    with _PARK_LOCK:
        _PUBLISHED[id(table)] = (weakref.ref(tree), table)


def release_published() -> None:
    """Swap every queued device table for its host copy in the document
    that still holds it, and drop the rest (their documents committed
    again or are gone).  Called before each kernel merge, so the merge
    gets the device memory they held."""
    with _PARK_LOCK:
        queued = list(_PUBLISHED.values())
        _PUBLISHED.clear()
    for ref, table in queued:
        tree = ref()
        if tree is not None and tree._table is table:
            tree._swap_table(table, view_mod.to_host(table))


class MatzWarning(UserWarning):
    """A persisted materialization artifact (``matz-*.npz``) was
    present but unusable — corrupt, truncated, or inconsistent with
    the restored log.  The restore FALLS BACK to the full first-merge
    materialization (slow but always correct) and warns with this
    type so operators can see cold paths silently losing their
    O(tail) guarantee.  Never affects data correctness."""


def matz_enabled() -> bool:
    """The ``GRAFT_MATZ`` kill switch (default on)."""
    return os.environ.get("GRAFT_MATZ", "1").strip() != "0"


def _routed_materialize(arrays, hints):
    """The single-merge kernel dispatch for every engine-driven
    materialization: the stock single-device kernel, or the ops-axis
    sharded path (parallel/opsaxis.py) when the GRAFT_OPSAXIS route is
    enabled and the candidate set is at or past
    GRAFT_OPSAXIS_MIN_OPS on a multi-device host.  The sharded table
    is bit-identical (shapes included — divisibility is part of the
    route gate), so commit rollback, ``last_applied_mask``
    attribution, fingerprints, and sync windows ride through
    unchanged (pinned by tests/test_opsaxis.py)."""
    from .parallel import opsaxis
    release_published()
    return opsaxis.routed_materialize(arrays, hints)


def _mode(p: PackedOps) -> Optional[str]:
    """Kernel hint mode for a packed batch: the cond-free "exhaustive"
    path when this engine's own ingest vouched for hint completeness
    (pack/concat/parse_pack provenance — auto and exhaustive are then
    semantically identical, and exhaustive compiles neither the sort nor
    the join); verified auto otherwise (e.g. restored checkpoints whose
    hint columns were defaulted).

    A violated vouch silently mis-resolves references (that is the mode's
    contract; VERDICT r3 weak-4), so ``GRAFT_DEBUG_VOUCH=1`` arms a
    host-side tripwire re-auditing every vouched batch before it reaches
    the cond-free trace — armed for the whole test suite in
    tests/conftest.py, so any producer bug that breaks the vouch
    invariant fails loudly there instead of corrupting a merge.

    The exhaustive mode's elementwise resolution consumes the
    host-elected ``win_row`` / ``parent_row`` slot-hint columns, which
    the tripwire above audits alongside the others
    (``derive_slot_hints`` is the single source for all six)."""
    if not p.hints_vouched:
        return None
    if os.environ.get("GRAFT_DEBUG_VOUCH"):
        if not packed_mod.verify_hints(p):
            raise RuntimeError(
                "hints_vouched batch failed the host hint audit — a "
                "producer (pack/concat/parse_pack/restore) broke the "
                "vouch invariant; the exhaustive kernel mode would "
                "silently mis-resolve")
        # the derived SLOT hints (the fused resolution's elementwise
        # columns) must agree with a fresh derivation from the audited
        # base columns: a stale cache (e.g. a producer mutating hint
        # columns after arrays() ran) would mis-resolve the same way
        if p.slot_hints is not None:
            fresh = packed_mod.derive_slot_hints(
                {k: getattr(p, k) for k in
                 ("kind", "ts", "parent_ts", "anchor_ts", "depth",
                  "paths", "parent_pos", "anchor_pos", "target_pos",
                  "ts_rank")})
            import numpy as _np
            if any(not _np.array_equal(p.slot_hints.get(k), fresh[k])
                   for k in fresh):
                raise RuntimeError(
                    "cached slot-hint columns diverge from the audited "
                    "base columns — stale derivation cache; the fused "
                    "exhaustive resolution would silently mis-resolve")
    return "exhaustive"


class StaleNodeView(RuntimeError):
    """A TableNode outlived the state it points into.

    Mirror slots are append-only, so views survive host-path edits; a
    kernel merge compacts and reassigns slots, and using a view across one
    would silently read a DIFFERENT node, so it fails loudly instead.
    Re-fetch with ``tree.get(node.path)``."""


class TableNode:
    """Read-only node view over the host mirror — the engine-side
    counterpart of the oracle ``Node`` facade (CRDTree/Node.elm): value,
    timestamp, path accessors and visible-children traversal, resolved
    from the mirror's slot arrays without any device round-trip.

    Views are tied to one slot assignment: a kernel merge (large batch
    apply) invalidates them (see :class:`StaleNodeView`)."""

    __slots__ = ("_tree", "_slot", "_gen")

    def __init__(self, tree: "TpuTree", slot: int):
        self._tree = tree
        self._slot = slot
        self._gen = tree._generation

    def _check(self) -> None:
        if self._gen != self._tree._generation:
            raise StaleNodeView(
                "node view predates the last kernel merge; re-fetch it "
                "with tree.get(path)")

    def _mirror(self) -> HostTree:
        self._check()
        return self._tree._ensure_mirror()

    @property
    def timestamp(self) -> int:
        m = self._mirror()
        if self.is_root or self._slot < 0:
            return 0
        return int(m.ts[self._slot])

    @property
    def path(self) -> Tuple[int, ...]:
        if self._slot < 0:
            # branch-head sentinel: ONE shared empty-path tombstone seeds
            # every children dict (Internal/Node.elm:46-48), so its path
            # accessor answers () regardless of where it was reached —
            # quirk preserved for oracle parity (core/node.py sentinel)
            return ()
        return self._mirror().path_of(self._slot)

    @property
    def is_root(self) -> bool:
        return self._slot == 0

    @property
    def is_deleted(self) -> bool:
        """Tombstoned directly OR gone with a deleted ancestor branch —
        either way the node left the document (a held view can observe
        this in place, since host edits don't invalidate views)."""
        if self._slot < 0:
            return True      # the branch-head sentinel IS a tombstone
        m = self._mirror()
        return bool(m.tomb[self._slot]) or m.is_dead(self._slot)

    @property
    def value(self) -> Any:
        """Value unless deleted or root (CRDTree/Node.elm:198-202)."""
        m = self._mirror()
        if self.is_root or self.is_deleted:
            return None
        return m.values[int(m.value_ref[self._slot])]

    def children(self) -> List["TableNode"]:
        """Visible children in document order; a deleted node's children
        left the tree with it."""
        return [TableNode(self._tree, s) for s in self._child_slots()]

    def _child_slots(self):
        m = self._mirror()
        if not self.is_root and self.is_deleted:
            return iter(())
        return m.iter_visible_children(self._slot)

    # -- traversal combinators over THIS node's children — the engine-side
    # face of the oracle facade (CRDTree/Node.elm:96-181; oracle spec
    # core/node.py:226-300), resolved from the mirror arrays, never via
    # to_oracle() ----------------------------------------------------------

    def foldl(self, func: Callable[["TableNode", Any], Any],
              acc: Any) -> Any:
        """Left fold over visible children (CRDTree/Node.elm:118-124)."""
        for s in self._child_slots():
            acc = func(TableNode(self._tree, s), acc)
        return acc

    def foldr(self, func: Callable[["TableNode", Any], Any],
              acc: Any) -> Any:
        """Right fold over visible children (CRDTree/Node.elm:127-133)."""
        for s in reversed(list(self._child_slots())):
            acc = func(TableNode(self._tree, s), acc)
        return acc

    def map(self, func: Callable[["TableNode"], Any]) -> List[Any]:
        """``func`` over visible children (CRDTree/Node.elm:101-105)."""
        return [func(TableNode(self._tree, s)) for s in self._child_slots()]

    def filter_map(self, func: Callable[["TableNode"], Any]) -> List[Any]:
        """Keep non-None results (CRDTree/Node.elm:108-115)."""
        out = []
        for s in self._child_slots():
            v = func(TableNode(self._tree, s))
            if v is not None:
                out.append(v)
        return out

    def loop(self, func: Callable[["TableNode", Any], Tuple[str, Any]],
             acc: Any) -> Any:
        """Left fold with early exit: ``func`` returns ("take", acc) to
        continue or ("done", acc) to stop (CRDTree/Node.elm:136-160)."""
        for s in self._child_slots():
            step, acc = func(TableNode(self._tree, s), acc)
            if step == "done":
                return acc
        return acc

    def find(self, pred: Callable[["TableNode"], bool]
             ) -> Optional["TableNode"]:
        """First CHAIN member matching ``pred`` — tombstones are candidates
        too: the reference's findHelp follows raw next pointers without
        skipping (Internal/Node.elm:166-183)."""
        m = self._mirror()
        if not self.is_root and self.is_deleted:
            return None
        for s in m.iter_siblings(self._slot):
            n = TableNode(self._tree, s)
            if pred(n):
                return n
        return None

    def head(self) -> Optional["TableNode"]:
        """First visible child (CRDTree/Node.elm:163-166)."""
        for s in self._child_slots():
            return TableNode(self._tree, s)
        return None

    def last(self) -> Optional["TableNode"]:
        """Last visible child (CRDTree/Node.elm:169-172)."""
        out = None
        for s in self._child_slots():
            out = s
        return TableNode(self._tree, out) if out is not None else None

    def descendant(self, path: Sequence[int]) -> Optional["TableNode"]:
        """Node at ``path`` relative to this node, by child timestamps —
        O(len(path)) via the mirror's timestamp index
        (Internal/Node.elm:289-299; CRDTree/Node.elm:175-181).  Can land ON
        a tombstone (they keep their position) but not descend through
        one (their children left the tree)."""
        if not path:
            return None
        m = self._mirror()
        if not self.is_root and self.is_deleted:
            return None
        cur = self._slot
        for i, ts in enumerate(path):
            if i > 0 and m.tomb[cur]:
                return None
            s = m.ts2slot.get(int(ts))
            if s is None or m.parent[s] != cur:
                return None
            cur = s
        return TableNode(self._tree, int(cur))

    def __eq__(self, other) -> bool:
        # generation participates: a stale view must not compare equal to a
        # live view that happens to reuse its slot number
        return isinstance(other, TableNode) and other._slot == self._slot \
            and other._tree is self._tree and other._gen == self._gen

    def __hash__(self) -> int:
        return hash((id(self._tree), self._slot, self._gen))

    def __repr__(self) -> str:
        if self.is_root:
            return "TableNode(root)"
        try:
            return (f"TableNode(ts={self.timestamp}, path={self.path}, "
                    f"value={self.value!r})")
        except StaleNodeView:
            return f"TableNode(stale, slot={self._slot})"


class TpuTree:
    """Array-backed replica.  See module docstring."""

    def __init__(self, replica: int, max_depth: int = DEFAULT_MAX_DEPTH):
        self._replica = replica
        self._timestamp = ts_mod.make(replica, 0)
        self._cursor: Tuple[int, ...] = (0,)
        # chronological, applied ops only; columnar segments (oplog.py)
        # so bulk ingest never builds per-op objects
        self._log = OpLog()
        self._replicas: dict = {}
        self._last_operation: Operation = Batch(())
        self._max_depth = max_depth
        self._table: Optional[NodeTable] = None
        # the value list ``_table.value_ref`` indexes (the packing it was
        # merged from; a spill may drop ``_packed`` while the table stays)
        self._table_values: Optional[List[Any]] = None
        self._packed: Optional[PackedOps] = None
        self._mirror: Optional[HostTree] = None
        self._batch_depth = 0
        # bumped whenever mirror slots are reassigned (kernel merges);
        # TableNode captures it at construction so stale views fail loudly
        self._generation = 0
        # per-leaf applied mask of the last successful apply — the serving
        # scheduler's attribution channel for fused multi-client batches
        self._last_applied_mask: Optional[np.ndarray] = None
        # persisted materialization (docs/DURABILITY.md): True when a
        # restore found a matz artifact in the manifest — the first
        # mirror build loads it and replays only the tail instead of
        # merging the whole history
        self._matz_pending = False
        self.matz_stats: dict = {"writes": 0, "loads": 0,
                                 "fallbacks": 0, "tail_replayed": 0}

    # -- identity / clocks (parity: CRDTree.elm:130-139, 337-350) ---------

    @property
    def replica_id(self) -> int:
        return self._replica

    @property
    def id(self) -> int:
        """Reference-named alias of :attr:`replica_id` (CRDTree.elm `id`)."""
        return self._replica

    @property
    def timestamp(self) -> int:
        return self._timestamp

    @property
    def cursor(self) -> Tuple[int, ...]:
        return self._cursor

    @property
    def last_operation(self) -> Operation:
        return self._last_operation

    @property
    def last_applied_mask(self) -> Optional[np.ndarray]:
        """Boolean per-leaf mask over the last successful
        ``apply``/``apply_packed`` batch, in submitted order: True where
        the leaf APPLIED, False where it absorbed as a duplicate.  Lets
        a caller that FUSED several independent deltas into one batch
        (serve/scheduler.py) attribute applied counts back to each
        delta's row span without materializing op objects.  None before
        the first apply; undefined after a raising apply."""
        return self._last_applied_mask

    @property
    def log_length(self) -> int:
        """Applied-op count, O(1) (the op log IS the state)."""
        return len(self._log)

    def next_timestamp(self) -> int:
        return self._timestamp + 1

    def last_replica_timestamp(self, replica: int) -> int:
        return self._replicas.get(replica, 0)

    # -- the materialised view -------------------------------------------

    def _view_table(self) -> Tuple[NodeTable, List[Any]]:
        """The materialized view and the value list its ``value_ref``
        indexes, left where it lives: a parked kernel table as it is
        (device, sharded or host), else one kernel merge of the log,
        parked on the device."""
        if self._table is None:
            p = self._ensure_packed()
            self._park(_routed_materialize(p.arrays(), hints=_mode(p)),
                       p.values)
        return self._table, self._table_values

    def _park(self, table: Optional[NodeTable],
              values: Optional[List[Any]]) -> None:
        with _PARK_LOCK:
            self._table, self._table_values = table, values

    def _swap_table(self, table: NodeTable, host: NodeTable) -> None:
        """Put ``host``, the host copy of ``table``, in its place, unless
        a commit replaced ``table`` meanwhile."""
        with _PARK_LOCK:
            if self._table is table:
                self._table = host

    def table(self) -> NodeTable:
        """The converged node table (host numpy); re-materialised lazily
        through the batched kernel from the op log.

        A bulk ingest (apply_packed/_apply_kernel) parks the DEVICE
        table here after reading back only the status column, and the
        publish reads the visible sequence from it without converting
        it (:meth:`visible_values`).  That read starts the full
        ~15-column host copy in the background, and the next kernel
        merge in the process swaps it in (:func:`release_published`).
        Until then, the copy is paid here, on the first host-path edit
        or node-API read of a kernel-merged document, when the host
        mirror is built from it; the conversion then caches."""
        table, _ = self._view_table()
        if not isinstance(table.status, np.ndarray):
            host = view_mod.to_host(table)
            self._swap_table(table, host)
            table = host
        return table

    def _ensure_mirror(self) -> HostTree:
        """The host mirror, built lazily: from a persisted
        materialization artifact + tail replay when a tiered restore
        left one pending (O(tail since artifact) — the cold-path
        collapse), from an existing table when one is materialised,
        through the kernel for big logs, by sequential replay for
        small ones.  A rebuild is the ``engine.mirror`` span and, for a
        document with ops, counts in ``mirror_builds``."""
        if self._mirror is None:
            with profiling.span("engine.mirror", ops=len(self._log)):
                self._mirror = self._build_mirror()
            if len(self._log):
                _count_view("mirror_builds")
        return self._mirror

    def _mirror_from_table(self) -> bool:
        """True when :meth:`_build_mirror` would build from the kernel
        table: one is parked, or the log is past DELTA_THRESHOLD with no
        pending materialization artifact to load instead."""
        return self._table is not None or (
            not self._matz_pending and len(self._log) > DELTA_THRESHOLD)

    def _build_mirror(self) -> HostTree:
        if not self._mirror_from_table():
            if self._matz_pending:
                m = self._load_matz_mirror()
                if m is not None:
                    return m
            if len(self._log) <= DELTA_THRESHOLD:
                m = HostTree(self._max_depth)
                for op in self._log:
                    if isinstance(op, Add):
                        m.apply_add(op.ts, tuple(op.path), op.value)
                    else:
                        m.apply_delete(tuple(op.path))
                m.journal.clear()
                return m
        table = self.table()
        return HostTree.from_table(table, self._table_values,
                                   self._max_depth)

    def _stale_device(self) -> None:
        """Host-path edit: device view no longer matches the log; mirror
        (and outstanding views) stay valid."""
        self._park(None, None)
        self._packed = None

    def _invalidate(self) -> None:
        """Full invalidation: slots will be reassigned — views go stale."""
        self._park(None, None)
        self._packed = None
        self._mirror = None
        self._generation += 1

    # -- remote application (parity: CRDTree.elm:235-295) -----------------

    def apply(self, operation: Operation) -> "TpuTree":
        """Apply a remote operation/batch atomically; cursor unmoved.

        Small deltas (≤ DELTA_THRESHOLD leaves) apply sequentially on the
        host mirror in O(delta) — the reference's own per-op cost
        (Internal/Node.elm:51-104) — rolled back via the undo journal on
        failure.  Large deltas go through :meth:`_apply_bulk`: host-first
        in O(delta) when the delta is small relative to the document,
        kernel set-join over the whole candidate log otherwise (or when
        sequential application rejects a shuffled valid set); per-op
        statuses decide what enters the log.  Either way duplicates and edits under deleted branches are
        absorbed, and any NotFound/InvalidPath in the batch raises and
        leaves the replica untouched — reference batch atomicity
        (tests/CRDTreeTest.elm:482-498).

        Reorder contract (pinned by tests/test_reorder_semantics.py):
        small batches have SEQUENCE semantics — reference-exact errors
        under any permutation; large batches have SET semantics — bulk
        anti-entropy absorbs any arrival order of a valid add set
        (deletes stay order-sensitive: one placed before its target's
        add fails the batch).
        """
        leaves = list(op_mod.iter_leaves(operation))
        if not leaves:
            self._last_operation = Batch(())
            self._last_applied_mask = np.zeros(0, dtype=bool)
            return self
        if len(leaves) <= DELTA_THRESHOLD:
            applied = self._apply_host(leaves)
        else:
            applied = self._apply_bulk(leaves)
        self._last_operation = (
            applied[0] if len(leaves) == 1 and applied
            else Batch(tuple(applied)))
        # the clock advances once per Add carrying our own replica id —
        # including absorbed duplicates, and including Adds arriving through
        # remote apply (reference: incrementTimestamp runs on the Ok path,
        # CRDTree.elm:275-282, 318-319, 337-343)
        own_adds = sum(1 for op in leaves
                       if isinstance(op, Add)
                       and ts_mod.replica_id(op.ts) == self._replica)
        self._timestamp += own_adds
        self._after_commit()
        return self

    # -- cascade tiering (oplog.py) ---------------------------------------

    def enable_log_tiering(self, dir: str, *, hot_ops: int = 32768,
                           hot_bytes: int = 0, gc_min_segs: int = 4,
                           auto_stable: bool = True,
                           cache_segments: int = 2,
                           ephemeral: bool = False,
                           durable: bool = False,
                           cache_mb: Optional[int] = None,
                           base_chunk_ops: Optional[int] = None,
                           cache=None) -> "TpuTree":
        """Arm the op log's three-tier cascade (oplog module
        docstring): hot ops past the budget spill to packed-npz
        segments under ``dir`` at commit boundaries, a stability-
        watermark-gated GC folds them into a checkpoint base, and the
        full-packing cache drops whenever columns leave memory (it
        would otherwise keep the whole history resident and defeat the
        point)."""
        self._log.enable_tiering(
            dir, hot_ops=hot_ops, hot_bytes=hot_bytes,
            gc_min_segs=gc_min_segs, auto_stable=auto_stable,
            cache_segments=cache_segments, ephemeral=ephemeral,
            max_depth=self._max_depth, on_spill=self._on_log_spill,
            durable=durable, cache_mb=cache_mb,
            base_chunk_ops=base_chunk_ops, cache=cache)
        return self

    def begin_commit(self) -> tuple:
        """Snapshot the pre-commit state the WAL shed path needs to
        roll one commit back (serve/scheduler.py): a merge whose WAL
        record cannot be made durable (ENOSPC/EIO) must leave the
        replica untouched, or the log would hold ops that exist in
        neither the tiers nor the WAL — and a later acked write could
        causally depend on them, turning a disk hiccup into acked loss
        at the next crash."""
        return (len(self._log), self._timestamp, dict(self._replicas),
                self._last_operation)

    def rollback_commit(self, saved: tuple) -> None:
        """Undo everything since :meth:`begin_commit`: truncate the
        log, restore clocks and provenance, invalidate the materialized
        view."""
        n0, timestamp, replicas, last_op = saved
        self._log.truncate(n0)
        self._timestamp = timestamp
        self._replicas = replicas
        self._last_operation = last_op
        self._invalidate()

    def manifest_meta(self) -> dict:
        """The clock/cursor meta a durable tier manifest carries —
        exactly what :meth:`checkpoint_tiered` persists, minus the
        last-operation span (a LIVE manifest is written mid-flight at
        spill boundaries; WAL replay rebuilds ``last_operation`` from
        the final record, so the span would be dead weight)."""
        return {
            "replica": self._replica,
            "timestamp": self._timestamp,
            "cursor": list(self._cursor),
            "replicas": {str(k): v for k, v in self._replicas.items()},
            "max_depth": self._max_depth,
        }

    def _on_log_spill(self) -> None:
        # resident columns moved to disk: holding the monolithic
        # packing would pin them all in memory anyway
        self._packed = None

    def log_view(self):
        """A reference-stable :class:`~crdt_graph_tpu.oplog.LogView`
        of the applied log — what a published read snapshot pins
        (serve/snapshot.py)."""
        return self._log.view(self._max_depth)

    def _after_commit(self) -> None:
        """Commit-boundary hook: run the cascade's spill/GC unless a
        batch is mid-flight (its rollback path truncates back into what
        must still be the hot tier).  The ``engine.spill`` span, inside
        ``engine.finish`` on a kernel apply."""
        if self._batch_depth == 0:
            with profiling.span("engine.spill"):
                self._log.maybe_spill()

    def _apply_host(self, leaves: List[Operation]) -> List[Operation]:
        """Sequential host-path apply; first failure rolls everything back
        and raises (the oracle stops there too, CRDTree.elm:224-232)."""
        m = self._ensure_mirror()
        sp = m.savepoint()
        applied: List[Operation] = []
        mask = np.zeros(len(leaves), dtype=bool)
        for i, op in enumerate(leaves):
            if isinstance(op, Add):
                st = m.apply_add(op.ts, tuple(op.path), op.value)
            else:
                st = m.apply_delete(tuple(op.path))
            if st == NOT_FOUND:
                m.rollback(sp)
                raise OperationFailedError(op)
            if st == INVALID_PATH:
                m.rollback(sp)
                raise InvalidPathError(f"invalid path in {op!r}")
            if st == APPLIED:
                applied.append(op)
                mask[i] = True
        self._record(applied)
        self._last_applied_mask = mask
        if applied:
            self._stale_device()
        if self._batch_depth == 0:
            m.journal.clear()
        return applied

    def _apply_bulk(self, leaves: List[Operation]) -> List[Operation]:
        """Bulk (> DELTA_THRESHOLD) apply without the re-materialisation
        cliff (VERDICT r2 weak-3): a causally ordered bulk delta — what
        ``operations_since`` anti-entropy actually delivers — applies
        through the O(delta) host mirror, so serving cost scales with the
        DELTA, not the document.  Only when sequential application fails
        (a shuffled valid set: anchors arriving after their dependants)
        does it fall back to the kernel set-join over log+delta, keeping
        the large-batch SET-semantics contract
        (tests/test_reorder_semantics.py) bit-for-bit: the fallback
        absorbs exactly the batches the kernel path always absorbed, and
        genuinely-invalid batches raise from the kernel statuses as
        before.  Host-first is skipped when the delta rivals the document
        itself (Python per-op cost would exceed one vectorised merge)."""
        if len(leaves) < max(4 * DELTA_THRESHOLD, len(self._log) // 8):
            try:
                return self._apply_host(leaves)
            except (OperationFailedError, InvalidPathError):
                pass    # rolled back; retry as an unordered set
        return self._apply_kernel(leaves)

    def apply_wire(self, payload) -> "TpuTree":
        """Remote apply straight from wire JSON (str or bytes).

        Interactive-size deltas decode to op objects and keep the
        sequence-semantics path of :meth:`apply`.  Bootstrap-size
        batches skip the wire → objects → columns round trip that
        dominated ``POST /ops`` at 1M ops (scripts/bench_service_e2e.py):
        native parse to columns, one kernel set-join, and vectorized
        clock bookkeeping from the columns; op objects are built once,
        for the log.  Raises exactly what :meth:`apply` raises (the
        service's 400/409 contract is unchanged)."""
        from . import native
        from .codec import json_codec

        def _object_path():
            text = payload.decode() if isinstance(payload, bytes) \
                else payload
            return self.apply(json_codec.loads(text))

        if not native.available():
            return _object_path()
        return self.apply_packed(
            native.parse_pack(payload, max_depth=self._max_depth))

    def apply_packed(self, pnew: PackedOps) -> "TpuTree":
        """Remote apply from already-packed columns (the ingest fast
        path's second half — see :meth:`apply_wire`).  A delta that
        routes to the kernel merges in ONE launch over log ∪ delta,
        whatever its size: SET semantics over the whole batch, atomic,
        raising exactly what :meth:`apply` raises.  Splitting a push
        over several calls converges to the same state bit for bit
        (tests/test_serving.py), but each call re-merges the whole log,
        so the scheduler never splits one."""
        # below the bulk kernel crossover, keep apply()'s exact
        # sequence-semantics routing (host path / host-first)
        if not self.packed_route(pnew.num_ops):
            return self.apply(op_mod.from_list(packed_mod.unpack(pnew)))
        p = self.prepare_packed(pnew)
        # device table; only the status column reads back here (table()
        # converts the rest lazily, off the serving path).  The launch
        # span holds the dispatch and the host-to-device copy; the
        # device work itself is waited for in finish_packed
        with profiling.span("engine.launch", rows=p.num_ops):
            table = _routed_materialize(p.arrays(), hints=_mode(p))
        return self.finish_packed(pnew, p, table)

    def packed_route(self, n: int) -> bool:
        """True when a packed delta of ``n`` leaves takes the bulk kernel
        (prepare/materialize/finish); False routes through :meth:`apply`'s
        sequence-semantics object path.  Exposed so the serving scheduler
        (serve/scheduler.py) can group same-round kernel launches across
        documents into one batched materialization."""
        return n >= max(4 * DELTA_THRESHOLD, len(self._log) // 8)

    def prepare_packed(self, pnew: PackedOps) -> PackedOps:
        """Stage 1 of the staged kernel apply: the candidate column set
        (current log ∪ delta) whose materialization yields the new view,
        with its slot hints derived (``p.arrays()`` caches them on the
        set; every launch path reads them).  Callers either materialize
        it themselves (possibly batched with other documents —
        parallel.mesh.batched_materialize) and hand the table to
        :meth:`finish_packed`, or just call :meth:`apply_packed`.  The
        ``engine.prepare`` span, which first swaps the tables earlier
        visible reads left on the device for their host copies
        (:func:`release_published`)."""
        with profiling.span("engine.prepare",
                            rows=len(self._log) + pnew.num_ops):
            release_published()
            p = packed_mod.concat(self._ensure_packed(), pnew)
            p.arrays()
        return p

    def finish_packed(self, pnew: PackedOps, p: PackedOps,
                      table: NodeTable) -> "TpuTree":
        """Stage 2 of the staged kernel apply: per-op status check, clock
        bookkeeping, columnar log commit, and view parking for a table
        materialized from :meth:`prepare_packed`'s candidate set.  Raises
        exactly what :meth:`apply` raises, leaving the replica untouched.
        The status readback, which waits for the device, is the
        ``engine.sync`` span; the rest is ``engine.finish``, which also
        counts the delta's Deletes the kernel applied."""
        n = pnew.num_ops
        n0 = len(self._log)
        with profiling.span("engine.sync", rows=p.num_ops):
            st = np.asarray(table.status)[n0:n0 + n]
        with profiling.span("engine.finish", rows=n):
            self._commit_statuses(pnew, p, table, st)
            _count_view("kernel_deletes_applied", int(np.count_nonzero(
                (pnew.kind[:n] == packed_mod.KIND_DELETE) & (st == APPLIED))))
        return self

    def _commit_statuses(self, pnew: PackedOps, p: PackedOps,
                         table: NodeTable, st: np.ndarray) -> None:
        """:meth:`finish_packed` once the delta's statuses ``st`` are on
        the host."""
        n = pnew.num_ops
        failing = np.nonzero((st == NOT_FOUND) | (st == INVALID_PATH))[0]
        if failing.size:
            k = int(failing[0])
            bad = packed_mod.unpack_rows(pnew, k, k + 1)[0]
            if st[k] == NOT_FOUND:
                raise OperationFailedError(bad)
            raise InvalidPathError(f"invalid path in {bad!r}")

        # vectorized _record: replica clocks from the columns.  Reference
        # semantics are LAST-APPLIED-WINS per replica (updateTree stores
        # each applied op's timestamp, CRDTree.elm:298-316 — which can
        # regress a clock when a log delivers a replica's ops out of ts
        # order), and a Delete updates the TARGET timestamp's replica
        # (the op's ts IS the target's, Internal/Operation.elm:94-104);
        # the packed ts column already holds exactly that per kind.
        kind = pnew.kind[:n]
        ts_col = pnew.ts[:n]
        idx = np.nonzero(st == APPLIED)[0]
        ts_eff = ts_col[idx]
        rids = ts_eff >> 32
        uniq, inv = np.unique(rids, return_inverse=True)
        last = np.zeros(uniq.size, np.int64)
        np.maximum.at(last, inv, np.arange(idx.size))
        for k in range(uniq.size):
            self._replicas[int(uniq[k])] = int(ts_eff[last[k]])

        # columnar log commit (VERDICT r4 weak-2): the log extends by
        # COLUMN SEGMENTS and the result batch materializes lazily — no
        # per-op Python objects anywhere on this path
        if idx.size == n:
            self._log.extend_packed(pnew)
            self._last_operation = PackedBatch(pnew)
            self._commit_view(True, p, table)
        elif idx.size:
            # keep only the applied rows (columnar)
            sel = packed_mod.select_rows(pnew, idx)
            self._log.extend_packed(sel)
            self._last_operation = PackedBatch(sel)
            self._commit_view(False, p, table)
        else:
            # everything absorbed: log and view unchanged
            self._last_operation = Batch(())
        # own-op clock: every own-replica Add in the BATCH advances it,
        # absorbed duplicates included (apply() counts leaves the same)
        self._timestamp += int(np.sum(
            (kind == packed_mod.KIND_ADD) &
            ((ts_col >> 32) == self._replica)))
        self._last_applied_mask = np.asarray(st == APPLIED)
        self._after_commit()

    def _apply_kernel(self, leaves: List[Operation]) -> List[Operation]:
        p = packed_mod.concat(self._ensure_packed(),
                              packed_mod.pack(leaves,
                                              max_depth=self._max_depth))
        table = _routed_materialize(p.arrays(), hints=_mode(p))
        n0 = len(self._log)
        st = np.asarray(table.status)[n0:n0 + len(leaves)]
        failing = np.nonzero((st == NOT_FOUND) | (st == INVALID_PATH))[0]
        if failing.size:
            # report the FIRST failing op in batch order, by its own error —
            # the oracle stops there (CRDTree.elm:224-232)
            k = int(failing[0])
            if st[k] == NOT_FOUND:
                raise OperationFailedError(leaves[k])
            raise InvalidPathError(f"invalid path in {leaves[k]!r}")
        applied = [op for op, s in zip(leaves, st) if s == APPLIED]
        self._commit(applied, len(leaves) == len(applied), p, table)
        self._last_applied_mask = np.asarray(st == APPLIED)
        return applied

    def _record(self, applied: List[Operation]) -> None:
        for op in applied:
            ts = op_mod.op_timestamp(op)
            if ts is not None:
                self._replicas[ts_mod.replica_id(ts)] = ts
        self._log.extend(applied)

    def _commit(self, applied: List[Operation], all_applied: bool,
                p: PackedOps, table: NodeTable) -> None:
        self._record(applied)
        if applied:
            self._commit_view(all_applied, p, table)
        # else: view unchanged

    def _commit_view(self, all_applied: bool, p: PackedOps,
                     table: NodeTable) -> None:
        """View bookkeeping shared by the object (:meth:`_commit`) and
        columnar (:meth:`apply_packed`) kernel commits: a fully-applied
        batch's candidate packing == the new log packing, so the view is
        reused (mirror slots are reassigned — outstanding views go
        stale); a partial apply leaves absorbed ops in the candidate
        arrays but not the log, so value_ref indices would skew —
        re-materialise from the log on next read."""
        if all_applied:
            self._park(table, p.values)
            self._packed = p
            self._mirror = None
            self._generation += 1
        else:
            self._invalidate()

    # -- local edits (parity: CRDTree.elm:142-232) ------------------------

    def add(self, value: Any) -> "TpuTree":
        return self.add_after(self._cursor, value)

    def add_after(self, path: Sequence[int], value: Any) -> "TpuTree":
        op = Add(self.next_timestamp(), tuple(path), value)
        self._apply_local(op)
        return self

    def add_branch(self, value: Any) -> "TpuTree":
        self.add(value)
        self._cursor = self._cursor + (0,)
        return self

    def delete(self, path: Sequence[int]) -> "TpuTree":
        path = tuple(path)
        prev_path = self._predecessor_path(path)
        self._apply_local(Delete(path))
        if self._slot_at(prev_path) is not None or prev_path == path:
            self._cursor = prev_path
        return self

    def batch(self, funcs: Iterable[Callable[["TpuTree"], "TpuTree"]]
              ) -> "TpuTree":
        """Atomic local batch; accumulated last_operation like the oracle."""
        # the log is append-only inside a batch, so snapshot by length —
        # copying it would make every 1-op local edit O(log)
        log_len0 = len(self._log)
        saved = (self._timestamp, self._cursor,
                 dict(self._replicas), self._last_operation)
        m0 = self._ensure_mirror()
        sp = m0.savepoint()
        # a func that edits nothing must contribute nothing — the oracle
        # resets the accumulator before folding (core/tree.py batch)
        self._last_operation = Batch(())
        acc: List[Operation] = []
        self._batch_depth += 1
        try:
            for f in funcs:
                f(self)
                acc.extend(op_mod.to_list(self._last_operation))
        except Exception:
            self._log.truncate(log_len0)
            (self._timestamp, self._cursor,
             self._replicas, self._last_operation) = saved
            if self._mirror is m0 and len(m0.journal) >= sp:
                # every edit since the savepoint was host-path: undo them
                # in place; outstanding views stay valid
                m0.rollback(sp)
                self._stale_device()
            else:
                # a kernel merge replaced the mirror mid-batch — rebuild
                # from the restored log
                self._invalidate()
            raise
        finally:
            self._batch_depth -= 1
        if self._batch_depth == 0 and self._mirror is not None:
            self._mirror.journal.clear()
        self._last_operation = Batch(tuple(acc))
        self._after_commit()
        return self

    def _apply_local(self, op: Operation) -> None:
        saved_cursor = self._cursor
        self.apply(op)
        ts = op_mod.op_timestamp(op)
        # cursor follows local edits (CRDTree.elm:298-316); absorbed ops
        # leave it in place
        if ts is not None and isinstance(op, (Add, Delete)):
            if op_mod.to_list(self._last_operation):
                self._cursor = tuple(op.path[:-1]) + (ts,)
            else:
                self._cursor = saved_cursor
        # clock advancement happens in apply()

    def _predecessor_path(self, path: Tuple[int, ...]) -> Tuple[int, ...]:
        """Predecessor for post-delete cursor placement, matching the
        reference's search (CRDTree.elm:199-216): the first chain member
        whose next-VISIBLE sibling is the target — i.e. the nearest visible
        predecessor, or the first tombstone of a leading tombstone run, or
        the target's own path when it heads the chain."""
        m = self._ensure_mirror()
        if path and path[-1] == 0:
            # branch-head sentinel target: the reference resolves it to the
            # branch's head TOMBSTONE (children dicts are seeded with
            # ``0 -> Tombstone``, Internal/Node.elm:48; descendant/child
            # return it, Internal/Node.elm:284-299), nothing's next-sibling
            # is ever the chain head, so pathPrevious defaults to the
            # target path (CRDTree.elm:199-216) — the delete itself then
            # absorbs as AlreadyApplied and the cursor stays put
            return path
        idx = m.get_slot(tuple(path))
        if idx is not None and m.tomb[idx]:
            # tombstoned target: the reference probe (next-visible == target)
            # never matches, cursor defaults to the target path
            return path
        if idx is None:
            # missing or dead target (oracle get() sees None either way):
            # the reference falls back to the root branch and matches the
            # first chain member with NO visible successor
            chain = list(m.iter_siblings(0))
            vis = [s for s in chain if not m.tomb[s]]
            if vis:
                return m.path_of(vis[-1])
            return m.path_of(chain[0]) if chain else path
        # visible target: nearest visible predecessor in its branch, else
        # the first tombstone of the leading run, else the target's own path
        p = m.prev_for(idx)
        return m.path_of(p) if p is not None else path

    # -- anti-entropy (parity: CRDTree.elm:390-418) -----------------------

    def operations_since(self, initial_timestamp: int) -> Operation:
        """Anti-entropy suffix (inclusive ``since`` terminator,
        Internal/Operation.elm:25-53; semantics pinned by test_tree.py).
        The log holds each add timestamp at most once (duplicates absorb
        before reaching it), so the suffix starts at the indexed
        position of the matching Add — only those rows materialize to
        objects (columnar log, oplog.OpLog)."""
        if initial_timestamp == 0:
            return self._log.as_batch()
        start = self._log.index_of_add(initial_timestamp)
        if start is None:
            return Batch(())
        return op_mod.from_list(
            self._log.materialize(start, len(self._log)))

    def dumps_since_bytes(self, initial_timestamp: int) -> bytes:
        """Wire JSON bytes for ``operations_since`` without per-op
        Python encode — :func:`packed_since_bytes` over the cached
        packed log.  Byte-identical to
        ``json_codec.dumps(self.operations_since(ts))`` (pinned by the
        differential suite in tests/test_native_codec.py).  Returned as
        bytes so the service can write the multi-megabyte bootstrap
        payload straight to the socket with no str round trip.  Without
        the native module, answers from the object log directly (no
        packed export of a host-path log just to re-encode it)."""
        from . import native
        from .codec import json_codec
        if not native.available():
            return json_codec.dumps(
                self.operations_since(initial_timestamp)).encode()
        return packed_since_bytes(self._ensure_packed(),
                                  initial_timestamp)

    def dumps_since(self, initial_timestamp: int) -> str:
        """:meth:`dumps_since_bytes` as text."""
        return self.dumps_since_bytes(initial_timestamp).decode()

    # -- queries ----------------------------------------------------------

    def _slot_at(self, path: Tuple[int, ...]) -> Optional[int]:
        """Slot of the node at ``path`` — tombstones included, discarded
        descendants of deleted branches excluded, matching the oracle's
        ``get`` (a tombstone's children leave the tree, core/tree.py:195).
        O(depth) via the mirror's timestamp index."""
        return self._ensure_mirror().get_slot(tuple(path))

    def get_value(self, path: Sequence[int]) -> Any:
        """Value at path; None if missing, deleted, or under a deleted
        branch."""
        m = self._ensure_mirror()
        s = m.get_slot(tuple(path))
        if s is None or s == 0 or m.tomb[s]:
            return None
        return m.values[int(m.value_ref[s])]

    def _ensure_packed(self) -> PackedOps:
        # read-once into a local: the background maintenance worker's
        # spill drops this cache (_on_log_spill) concurrently with the
        # scheduler thread calling here — a re-read after the null
        # would return None mid-merge.  A stale local is merely a
        # memory-footprint miss, never wrong data (the packing is
        # immutable).
        p = self._packed
        if p is None:
            # columnar segments union via concat — after a host edit on
            # a bootstrap-restored doc this is O(delta), not a per-op
            # re-pack of the whole history
            p = self._packed = self._log.to_packed(self._max_depth)
        return p

    def packed_state(self) -> PackedOps:
        """The whole applied log as one packed column set (cached between
        edits).  Callers must treat the result as IMMUTABLE — the serving
        engine (serve/snapshot.py) publishes it into lock-free read
        snapshots, so mutating it would corrupt concurrent readers."""
        return self._ensure_packed()

    def visible_values(self) -> List[Any]:
        """Visible values in document order — the render path.  With no
        host mirror, a document whose mirror would be built from the
        kernel table answers from the table itself: the visible slots'
        value refs read back (``engine.visible`` span, counted in
        ``visible_from_table``), and no mirror is built.  A table on the
        device stays there, its host copy started, until the next kernel
        merge swaps the copy in (:func:`release_published`).  A mirror
        freshly loaded from a materialization artifact answers from its
        persisted visible sequence (one list copy) until the first
        applied mutation invalidates it; any other mirror by a walk."""
        if self._mirror is None and self._mirror_from_table():
            with profiling.span("engine.visible", ops=len(self._log)):
                table, values = self._view_table()
                out = view_mod.visible_values(table, values)
                if not isinstance(table.status, np.ndarray):
                    _publish_device_table(self, table)
            _count_view("visible_from_table")
            return out
        m = self._ensure_mirror()
        if m.vis_cache is not None:
            return list(m.vis_cache)
        return [m.values[int(m.value_ref[s])] for s in m.iter_visible()]

    # -- node views and traversal (parity: CRDTree.elm:423-625) -----------

    def root(self) -> TableNode:
        return TableNode(self, 0)

    def get(self, path: Sequence[int]) -> Optional[TableNode]:
        """Node at ``path`` (tombstones included) or None.  A trailing-0
        path addresses the branch-head SENTINEL, which exists under the
        root and under every live node (children dicts are seeded with
        ``0 -> Tombstone``, Internal/Node.elm:46-48) but not under a
        tombstoned/dead prefix (a tombstone's children left the tree)."""
        path = tuple(path)
        if path and path[-1] == 0:
            if len(path) == 1:
                return TableNode(self, -1)
            m = self._ensure_mirror()
            s = m.get_slot(path[:-1])
            if s is not None and s != 0 and not m.tomb[s]:
                return TableNode(self, -1)
            return None
        slot = self._slot_at(path)
        return TableNode(self, slot) if slot is not None else None

    def parent(self, node: TableNode) -> Optional[TableNode]:
        """Parent of a node; the root for depth-1 nodes."""
        node._check()
        if node.is_root:
            return None
        if node._slot < 0:
            # the shared sentinel's stored path is (), whose parent
            # resolves to the root (CRDTree.elm:430-444 via empty path)
            return TableNode(self, 0)
        return TableNode(self, int(self._ensure_mirror().parent[node._slot]))

    def next(self, node: TableNode) -> Optional[TableNode]:
        """Next visible sibling (CRDTree.elm:563-568); O(tombstone run).
        A node in a deleted branch has no visible siblings — its whole
        chain left the tree."""
        node._check()
        m = self._ensure_mirror()
        if node.is_root or node._slot < 0 or m.is_dead(node._slot):
            return None
        s = m.nxt[node._slot]
        while s != NIL and m.tomb[s]:
            s = m.nxt[s]
        return TableNode(self, int(s)) if s != NIL else None

    def prev(self, node: TableNode) -> Optional[TableNode]:
        """Previous sibling, reference-faithfully (CRDTree.elm:573-577):
        the first chain member whose next visible sibling is ``node`` —
        the nearest visible predecessor when one exists, otherwise the
        FIRST tombstone of a leading tombstone run (the reference's raw
        ``find`` does not skip tombstone candidates)."""
        node._check()
        m = self._ensure_mirror()
        if node.is_root or node._slot < 0 or m.is_dead(node._slot):
            return None
        p = m.prev_for(node._slot)
        return TableNode(self, p) if p is not None else None

    def walk(self, func: Callable[[TableNode, Any], Tuple[str, Any]],
             acc: Any, start: Optional[TableNode] = None) -> Any:
        """Resumable depth-first fold over visible nodes in document order
        (CRDTree.elm:583-625), straight off the mirror's sibling lists —
        O(1) per visited node, with early exit.  ``start`` is exclusive:
        the walk resumes after ``start``'s subtree and covers the remainder
        of its sibling list (with full descents), matching the oracle."""
        if start is not None:
            start._check()
        m = self._ensure_mirror()
        if start is None or start.is_root:
            it = m.iter_visible()
        elif m.is_dead(start._slot):
            return acc          # start's whole chain left the tree
        else:
            it = m.iter_visible_after(start._slot)
        for s in it:
            step, acc = func(TableNode(self, s), acc)
            if step == "done":
                return acc
        return acc

    def visible_paths(self) -> List[tuple]:
        m = self._ensure_mirror()
        return [m.path_of(s) for s in m.iter_visible()]

    def move_cursor_up(self) -> "TpuTree":
        if len(self._cursor) > 1:
            self._cursor = self._cursor[:-1]
        return self

    def set_cursor(self, path: Sequence[int]) -> "TpuTree":
        """Reference setCursor validates with ``get`` (CRDTree.elm:551-558)
        — sentinel paths under live nodes are therefore valid targets."""
        path = tuple(path)
        if self.get(path) is None:
            raise NotFound(f"no node at {path!r}")
        self._cursor = path
        return self

    def __len__(self) -> int:
        return self._ensure_mirror().count_visible()

    def __repr__(self) -> str:
        return (f"TpuTree(replica={self._replica}, ops={len(self._log)}, "
                f"ts={self._timestamp})")

    # -- interop / persistence -------------------------------------------

    def to_oracle(self):
        """Replay into a full-API oracle ``CRDTree`` (persistent value)."""
        from .core.tree import CRDTree
        tree = CRDTree.init(self._replica)
        tree = tree.apply(self.operations_since(0))
        return tree._replace(timestamp=self._timestamp,
                             cursor=self._cursor)

    def checkpoint(self, path: str) -> None:
        """Persist the replica: the op log IS the checkpoint (reference
        contract: full state = replay operationsSince 0, CRDTree.elm:235-262)
        plus clocks and cursor.  Values must be JSON-encodable."""
        from .codec import json_codec
        import json
        state = {
            "replica": self._replica,
            "timestamp": self._timestamp,
            "cursor": list(self._cursor),
            "replicas": {str(k): v for k, v in self._replicas.items()},
            "log": json_codec.encode(Batch(tuple(self._log))),
            "last_operation": json_codec.encode(self._last_operation),
            "max_depth": self._max_depth,
        }
        with open(path, "w") as f:
            json.dump(state, f)

    @staticmethod
    def restore(path: str) -> "TpuTree":
        from .codec import json_codec
        import json
        with open(path) as f:
            state = json.load(f)
        tree = TpuTree(state["replica"], max_depth=state["max_depth"])
        tree._log = OpLog(json_codec.decode(state["log"]).ops)
        tree._timestamp = state["timestamp"]
        tree._cursor = tuple(state["cursor"])
        tree._replicas = {int(k): v for k, v in state["replicas"].items()}
        tree._last_operation = json_codec.decode(state["last_operation"])
        return tree

    def checkpoint_packed(self, path, compress: bool = True) -> None:
        """Binary checkpoint: the packed op columns plus clocks, written
        with numpy — the fast path for big logs (no per-op JSON).  Values
        must be JSON-encodable (they ride in one JSON sidecar field).
        Written to exactly ``path`` (a file handle sidesteps numpy's
        .npz-suffix appending); ``path`` may itself be a binary
        file-like object (the service's snapshot wire format streams
        this into the HTTP response).  ``compress=False`` trades ~6x
        size for ~10x less encode time — the wire-snapshot choice,
        where the document lock is held while encoding.

        Format note (ADVICE r4): since r4 the ``last_operation`` blob is
        omitted whenever the tail-span invariant holds (``last_op_span``
        replaces it), so r4+ checkpoints are NOT readable by r3-era
        ``restore_packed`` (KeyError on ``last_operation``).  Old
        checkpoints remain readable by new code
        (tests/test_checkpoint_compat.py); snapshot wire-format
        consumers must run the r4+ restore."""
        import json
        from .codec import json_codec
        p = self._ensure_packed()
        meta = {
            "replica": self._replica,
            "timestamp": self._timestamp,
            "cursor": list(self._cursor),
            "replicas": {str(k): v for k, v in self._replicas.items()},
            "max_depth": self._max_depth,
            "num_ops": p.num_ops,
            "hints_vouched": p.hints_vouched,
        }
        self._last_op_meta(meta)
        write_packed_npz(path, p, meta, compress=compress)

    def _last_op_meta(self, meta: dict) -> None:
        """Stamp ``last_operation`` provenance into a checkpoint
        ``meta`` — shared by :meth:`checkpoint_packed` and
        :meth:`checkpoint_tiered`, whose restore paths consume the
        same keys.  last_operation is (by construction of apply/batch)
        the ops just appended to the log, so persist the row SPAN, not
        the encoded blob — after a bootstrap-size merge the blob alone
        was larger than every column combined (73 MB at 1M ops).
        Anything that breaks the suffix invariant falls back to the
        full encode."""
        from .codec import json_codec
        from .oplog import ViewSpanBatch
        lo = self._last_operation
        if isinstance(lo, ViewSpanBatch):
            # a restored-then-unchanged tree: the span is already log
            # positions of THIS log — re-emit it O(1) instead of
            # materializing a possibly-cold-tier-sized batch twice
            # just to re-derive the numbers it carries
            meta["last_op_span"] = [lo._start, lo._stop]
            meta["last_op_bare"] = False
        elif isinstance(lo, PackedBatch) and self._log.tail_is(lo):
            # columnar commit: the batch IS the log's final column
            # segment by construction — O(1), no materialization
            meta["last_op_span"] = [len(self._log) - lo.num_leaves,
                                    len(self._log)]
            meta["last_op_bare"] = False
        else:
            leaves = op_mod.to_list(lo)
            k = len(leaves)
            tail = self._log[len(self._log) - k:] if k else []
            if len(tail) == k and (
                    all(a is b for a, b in zip(leaves, tail))
                    or leaves == tail):
                meta["last_op_span"] = [len(self._log) - k,
                                        len(self._log)]
                meta["last_op_bare"] = not isinstance(lo, Batch)
            else:
                meta["last_operation"] = json_codec.encode(lo)

    @staticmethod
    def restore_packed(path, replica: Optional[int] = None) -> "TpuTree":
        """Rebuild a tree from ``checkpoint_packed`` output; ``path`` may
        be a filesystem path or a binary file-like (e.g. a BytesIO over
        the service's ``GET /docs/{id}/snapshot`` response).

        ``replica`` adopts a NEW identity for the restored tree — the
        snapshot-bootstrap contract: a served snapshot carries the
        SERVER's replica id, so an editing client must restore under its
        own id (from ``POST /replicas``) or every snapshot-bootstrapped
        client would mint the same timestamps and their concurrent edits
        would collide (first-arrival dedup absorbing one silently)."""
        import struct
        import zipfile
        import zlib
        from .core.errors import CheckpointError
        if replica is not None:
            # validate the CALLER's id before the corrupt-file
            # translation below — a bad argument is not a bad snapshot
            ts_mod.make(replica, 0)
        # the corrupt-file translation covers ONLY the load/meta-parse/
        # column-extraction region (ADVICE r5): tree ASSEMBLY below runs
        # outside it, so a genuine bug in the restore path surfaces as
        # itself instead of masquerading as a corrupt checkpoint.  The
        # typed meta validation in _load_packed_parts is what makes that
        # split safe — assembly only consumes already-validated fields.
        try:
            p, meta, last_op = TpuTree._load_packed_parts(path)
        except (zipfile.BadZipFile, zlib.error, KeyError, IndexError,
                ValueError, TypeError, AttributeError,
                NotImplementedError, EOFError, struct.error) as e:
            # one typed failure for the zoo a corrupt/truncated/
            # hand-edited npz raises (TypeError/AttributeError cover
            # CRC-valid members whose JSON fields hold the wrong types);
            # genuine I/O errors (missing file) pass through
            raise CheckpointError(
                f"corrupt or unreadable checkpoint: "
                f"{type(e).__name__}: {e}") from e
        return TpuTree._assemble_restored(p, meta, last_op, replica)

    @staticmethod
    def _load_packed_parts(path):
        """Load + parse + validate a packed checkpoint: everything whose
        failure means "corrupt/truncated/hand-edited file".  Returns
        ``(p, meta, last_op)`` with every meta field assembly touches
        already type-checked, so :meth:`_assemble_restored` cannot raise
        on file content."""
        import json
        from .codec import json_codec
        z = np.load(path)
        meta = json.loads(bytes(z["meta"]).decode())
        # an inflated num_ops in a CRC-valid hand-edited meta must not
        # drive pad_arrays into an attacker-sized allocation (MemoryError
        # escapes the CheckpointError translation by design — a genuine
        # out-of-memory on a legitimate restore should surface as itself).
        # isinstance alone admits bools (num_ops=true restored as 1 op):
        # reject them explicitly (ADVICE r5).
        n = meta.get("num_ops")
        if not isinstance(n, int) or isinstance(n, bool) or \
                not (0 <= n <= int(z["kind"].shape[0])):
            raise ValueError(
                f"meta num_ops {n!r} inconsistent with "
                f"column length {int(z['kind'].shape[0])}")

        def _int(name, value):
            if not isinstance(value, int) or isinstance(value, bool):
                raise ValueError(f"meta {name} {value!r} is not an integer")
            return value

        # validate every field assembly consumes (the translation above
        # must keep covering wrong-typed hand-edits, per the corruption
        # fuzz in tests/test_engine.py, even though assembly now runs
        # outside it)
        ts_mod.make(_int("replica", meta["replica"]), 0)
        _int("timestamp", meta["timestamp"])
        if _int("max_depth", meta["max_depth"]) < 1:
            raise ValueError(f"meta max_depth {meta['max_depth']!r} < 1")
        if not isinstance(meta["cursor"], list):
            raise ValueError(f"meta cursor {meta['cursor']!r} not a list")
        meta["cursor"] = [_int("cursor entry", c) for c in meta["cursor"]]
        if not isinstance(meta["replicas"], dict):
            raise ValueError("meta replicas is not a mapping")
        meta["replicas"] = {int(k): _int("clock", v)
                            for k, v in meta["replicas"].items()}
        last_op = None
        if "last_op_span" in meta:
            span = meta["last_op_span"]
            if not (isinstance(span, list) and len(span) == 2):
                raise ValueError(f"meta last_op_span {span!r} malformed")
            s, e = (_int("last_op_span", x) for x in span)
            if not (0 <= s <= e <= n):
                raise ValueError(f"meta last_op_span {span!r} outside "
                                 f"the {n}-op log")
        else:
            last_op = json_codec.decode(meta["last_operation"])

        # files hold exactly num_ops rows (older ones: full capacity);
        # re-pad to the jit bucket so restored trees share trace caches
        # with pack-produced batches
        cols = {k: z[k] for k in
                ("kind", "ts", "parent_ts", "anchor_ts", "depth",
                 "paths", "value_ref", "pos")}
        for k in ("parent_pos", "anchor_pos", "target_pos", "ts_rank"):
            if k in z.files:
                cols[k] = z[k]
        cols = packed_mod.pad_arrays(cols, packed_mod._bucket(max(n, 1)))
        p = PackedOps(
            kind=cols["kind"], ts=cols["ts"],
            parent_ts=cols["parent_ts"],
            anchor_ts=cols["anchor_ts"], depth=cols["depth"],
            paths=cols["paths"],
            value_ref=cols["value_ref"], pos=cols["pos"],
            values=json.loads(bytes(z["values"]).decode()),
            num_ops=n,
            # older checkpoints lack hint columns: pad_arrays/__post_init__
            # fill -1 and the kernel's join fallback keeps semantics
            parent_pos=cols.get("parent_pos"),
            anchor_pos=cols.get("anchor_pos"),
            target_pos=cols.get("target_pos"),
            # persisted so the restore audit below covers rank staleness
            # (absent in older files: __post_init__ recomputes from ts)
            ts_rank=cols.get("ts_rank"),
            # provenance survives the round trip: a vouched writer's
            # complete hint columns keep restored trees on the cond-free
            # exhaustive path; absent meta (old files) stays unvouched
            hints_vouched=bool(meta.get("hints_vouched", False)))
        # the vouch rides in the same file as the columns it vouches for,
        # so a stale/hand-edited/corrupt checkpoint could pair a True flag
        # with wrong hints and silently mis-resolve under the cond-free
        # mode (ADVICE r3) — re-verify on host before honoring it, and
        # REBUILD rather than demote on failure: keeping corrupt hints
        # would route every later merge through the sort+join fallback
        if p.hints_vouched and not packed_mod.verify_hints(p):
            packed_mod.rebuild_hints(p)
        if last_op is None and meta.get("last_op_bare"):
            s, e = meta["last_op_span"]
            if e - s == 1:
                # materializing a row consumes the op columns (kind/
                # value_ref/values), which only the file vouches for —
                # so it belongs HERE, under the corrupt-file
                # translation, not in assembly
                last_op = packed_mod.unpack_rows(p, s, e)[0]
        return p, meta, last_op

    @staticmethod
    def _assemble_restored(p, meta, last_op, replica):
        """Build the tree from validated parts — outside the corrupt-
        checkpoint exception translation (see :meth:`restore_packed`)."""
        rid = meta["replica"] if replica is None else replica
        tree = TpuTree(rid, max_depth=meta["max_depth"])
        # columnar restore: the loaded columns ARE the log; objects
        # materialize only if an object-path consumer asks
        tree._log = OpLog()
        tree._log.extend_packed(p)
        tree._packed = p
        tree._cursor = tuple(meta["cursor"])
        tree._replicas = dict(meta["replicas"])
        if rid == meta["replica"]:
            tree._timestamp = meta["timestamp"]
        else:
            # adopting a new identity: the own-op clock restarts at this
            # replica's last timestamp seen in the log (0 ops -> counter
            # 0), NOT the writer's clock — two clients restoring the
            # same served snapshot must not mint colliding timestamps
            tree._timestamp = max(ts_mod.make(rid, 0),
                                  tree._replicas.get(rid, 0))
        if last_op is not None:
            tree._last_operation = last_op
        else:
            s, e = meta["last_op_span"]
            tree._last_operation = PackedBatch(p, s, e)
        return tree

    # -- persisted materialization (docs/DURABILITY.md §Cold paths) -------

    def _matz_cheap(self) -> bool:
        """True when the materialization artifact is derivable without
        a full-history merge: a mirror or a parked kernel table is in
        hand, an artifact is pending, or the log is small.  A matz write
        must never INTRODUCE the cold-path cost it exists to remove."""
        return self._mirror is not None or self._table is not None \
            or self._matz_pending or len(self._log) <= DELTA_THRESHOLD

    def _matz_export(self, copy: bool = False) -> Optional[dict]:
        """The materialization artifact's slot arrays (``arrs``), value
        list and counts, or None unless :meth:`_matz_cheap`.  With no
        mirror and a parked kernel table, straight from the table
        (:meth:`HostTree.table_slots`; no mirror is built, the path
        plane is not read); otherwise from the mirror, built if need
        be.  ``copy`` snapshots the mirror's live arrays (the table's
        are fresh)."""
        if not self._matz_cheap():
            return None
        if self._mirror is None and self._table is not None:
            table, values = self._table, self._table_values
            arrs, idx = HostTree.table_slots(table)
            n, order, refs = (np.asarray(c) for c in (
                table.num_visible, table.visible_order, table.value_ref))
            arrs["vis_refs"] = refs[order[:int(n)]].astype(np.int32)
            return {"arrs": arrs, "values": values, "n": idx.size + 1,
                    "nvis": int(n)}
        m = self._ensure_mirror()
        return {"arrs": m.export_arrays(copy=copy), "values": m.values,
                "n": m.n, "nvis": m.nvis}

    def _save_matz_npz(self, target: str, name: str, arrs: dict,
                       values: list, meta: dict, fsync: bool) -> None:
        """Serialize one materialization artifact (tmp + rename so a
        manifest-referenced artifact is never observed
        half-written)."""
        import json
        os.makedirs(target, exist_ok=True)
        path = os.path.join(target, name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, values=np.frombuffer(
                json.dumps(values).encode(), np.uint8),
                meta=np.frombuffer(json.dumps(meta).encode(),
                                   np.uint8),
                **arrs)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, path)
        self.matz_stats["writes"] += 1

    def _write_matz_file(self, target: str,
                         fsync: bool = False) -> Optional[dict]:
        """Write the materialization artifact (mirror slot arrays +
        values + visible sequence) into ``target`` and return its
        manifest entry ``{"file", "len"}``, or None when the artifact
        is not cheaply derivable (:meth:`_matz_export`)."""
        x = self._matz_export()
        if x is None:
            return None
        length = len(self._log)
        name = self._log.next_matz_name() \
            if self._log.tiering_enabled else "matz-g1.npz"
        meta = {"kind": "matz", "matz_len": length, "n": x["n"],
                "nvis": x["nvis"], "max_depth": self._max_depth,
                "values_len": len(x["values"])}
        self._save_matz_npz(target, name, x["arrs"], x["values"], meta,
                            fsync)
        return {"file": name, "len": length}

    def matz_snapshot(self) -> Optional[dict]:
        """The scheduler-thread half of the BACKGROUND materialization
        export (serve/workers.py): spill the whole hot tail first (the
        artifact's coverage must stay ≤ the tiered extent — the usual
        write_matz rule), then snapshot the mirror's slot arrays
        COPY-ON-EXPORT, so the maintenance worker can serialize the
        O(doc-state) artifact while this thread keeps applying ops to
        the live mirror.  The copies are flat memcpys + one pointer
        copy of the value table (values are immutable JSON leaves) —
        milliseconds where the serialize is seconds.  After a kernel
        commit the arrays come from the parked table, with no mirror
        built.  None when the artifact is not cheaply derivable (never
        introduces the cost it removes)."""
        log = self._log
        if not matz_enabled() or not log.tiering_enabled \
                or not self._matz_cheap():
            return None
        log.spill_all()
        x = self._matz_export(copy=True)
        return {"arrs": x["arrs"], "values": list(x["values"]),
                "n": x["n"], "nvis": x["nvis"], "len": len(log),
                "values_len": len(x["values"])}

    def export_matz(self, snap: dict) -> bool:
        """The worker-thread half: serialize a :meth:`matz_snapshot`
        to its artifact file and publish it atomically in the
        manifest.  If the log was truncated below the snapshot's
        coverage in the meantime (a shed rollback), the artifact is
        DISCARDED — it must never resurrect rolled-back ops."""
        from .wal import maybe_crash
        log = self._log
        cfg = log._cfg
        if cfg is None or snap is None:
            return False
        name = log.next_matz_name()
        meta = {"kind": "matz", "matz_len": int(snap["len"]),
                "n": snap["n"], "nvis": snap["nvis"],
                "max_depth": self._max_depth,
                "values_len": snap["values_len"]}
        self._save_matz_npz(cfg.dir, name, snap["arrs"],
                            snap["values"], meta, fsync=cfg.durable)
        # chaos site: artifact on disk, manifest not yet referencing
        # it — recovery from the old manifest ignores the stray file
        maybe_crash("mid-matz-write")
        try:
            log.note_matz(name, int(snap["len"]))
        except ValueError:
            try:
                os.remove(os.path.join(cfg.dir, name))
            except OSError:
                pass
            return False
        return True

    def write_matz(self) -> bool:
        """Serving-path materialization snapshot: spill the whole hot
        tail (so the artifact's coverage is ≤ the tiered extent — a
        restore always finds every covered op in the tiers, never in
        an unsynced WAL tail that might not have survived), write the
        artifact, and publish it atomically in the manifest.  Returns
        True when an artifact landed.  Requires tiering; no-op when
        the artifact is not cheaply derivable or ``GRAFT_MATZ=0``."""
        from .wal import maybe_crash
        log = self._log
        if not matz_enabled() or not log.tiering_enabled:
            return False
        if not self._matz_cheap():
            return False
        log.spill_all()
        cfg = log._cfg
        entry = self._write_matz_file(cfg.dir, fsync=cfg.durable)
        if entry is None:
            return False
        # chaos site: artifact on disk, manifest not yet referencing
        # it — recovery from the old manifest ignores the stray file
        maybe_crash("mid-matz-write")
        log.note_matz(entry["file"], entry["len"])
        return True

    def _load_matz_mirror(self) -> Optional[HostTree]:
        """Rebuild the mirror from the manifest's materialization
        artifact + an O(tail) replay of the ops past its coverage.
        Any inconsistency — corrupt/truncated/missing artifact, a
        coverage beyond the restored log, a tail op the artifact
        state rejects — falls back to the full first-merge path with
        a typed :class:`MatzWarning` and a counted fallback: stale is
        absorbed, wrong is impossible, slow is the worst case."""
        import json
        import struct
        import warnings
        import zipfile
        import zlib
        from .core.errors import CheckpointError
        self._matz_pending = False          # consume once
        log = self._log
        cfg = log._cfg
        entry = log.matz_entry
        if entry is None or cfg is None or not matz_enabled():
            return None
        length = int(entry["len"])
        try:
            if length > len(log):
                raise CheckpointError(
                    f"matz artifact covers {length} ops; restored "
                    f"log holds {len(log)}")
            z = np.load(os.path.join(cfg.dir, entry["file"]))
            meta = json.loads(bytes(z["meta"]).decode())
            if meta.get("kind") != "matz" \
                    or int(meta["matz_len"]) != length:
                raise ValueError(f"matz meta mismatch: {meta!r}")
            nvis = int(meta["nvis"])
            values = json.loads(bytes(z["values"]).decode())
            if not isinstance(values, list) \
                    or len(values) != int(meta["values_len"]):
                raise ValueError("matz value table inconsistent")
            arrs = {k: z[k] for k in
                    ("ts", "parent", "depth", "value_ref", "tomb",
                     "first", "nxt", "prv")}
            m = HostTree.from_arrays(arrs, values, self._max_depth,
                                     nvis)
            vis_refs = np.asarray(z["vis_refs"])
            if vis_refs.shape != (nvis,) or (nvis and (
                    int(vis_refs.min()) < 0
                    or int(vis_refs.max()) >= len(values))):
                raise ValueError("matz visible sequence inconsistent")
            vals_arr = np.empty(len(values), dtype=object)
            vals_arr[:] = values
            m.vis_cache = vals_arr[vis_refs].tolist()
            # tail replay: only the ops past the artifact's coverage
            # (loads only their covering chunks); duplicates absorb,
            # anything the artifact state rejects is inconsistency
            tail = log.materialize(length, len(log))
            for op in tail:
                if isinstance(op, Add):
                    st = m.apply_add(op.ts, tuple(op.path), op.value)
                else:
                    st = m.apply_delete(tuple(op.path))
                if st in (NOT_FOUND, INVALID_PATH):
                    raise CheckpointError(
                        f"matz tail replay rejected {op!r}")
            m.journal.clear()
        except (CheckpointError, OSError, zipfile.BadZipFile,
                zlib.error, KeyError, IndexError, ValueError,
                TypeError, AttributeError, EOFError,
                struct.error) as e:
            self.matz_stats["fallbacks"] += 1
            warnings.warn(
                f"materialization artifact unusable "
                f"({type(e).__name__}: {e}); falling back to the "
                f"full first-merge materialization", MatzWarning,
                stacklevel=3)
            return None
        self.matz_stats["loads"] += 1
        self.matz_stats["tail_replayed"] += len(log) - length
        return m

    def checkpoint_tiered(self, dir: str,
                          write_matz: bool = True) -> str:
        """Tiered checkpoint: the cascade's base chunks + cold
        segments stay where they are, the hot tail spills to one final
        segment, and a ``manifest.json`` (tier layout + clocks/cursor
        meta) makes the directory self-describing — so restore is
        *checkpoint + tail* (descriptor opens, O(tail) work) instead
        of a full-history replay.  An untiered tree enables the
        cascade at ``dir`` first (non-ephemeral: a checkpoint must
        survive its writer).

        ``write_matz`` (and ``GRAFT_MATZ``): also persist the
        MATERIALIZED state artifact when the mirror/table is already
        in hand, so the restored document's FIRST READ is O(tail)
        too, not one full-history merge.  Skipped silently when
        deriving it would itself cost a full merge.

        ``last_operation`` is NOT persisted (same policy as the served
        snapshot wire format): a restoring consumer is bootstrapping,
        not resuming a half-open batch.  Returns the manifest path.

        ``dir`` is honored even when the cascade is already armed
        elsewhere (a served document tiers into ephemeral engine
        scratch): the segment files are then COPIED into ``dir``, so
        the checkpoint survives the engine that wrote it."""
        if not self._log.tiering_enabled:
            self.enable_log_tiering(dir, ephemeral=False)
        meta = self.manifest_meta()
        # persist last_operation provenance (shared _last_op_meta
        # policy with checkpoint_packed): a restored node's op
        # provenance then survives the round trip instead of silently
        # resetting to an empty batch (ISSUE 9 satellite)
        self._last_op_meta(meta)
        matz_entry = None
        if write_matz and matz_enabled():
            cfg = self._log._cfg
            matz_entry = self._write_matz_file(
                dir, fsync=cfg.durable if cfg is not None else False)
        path = self._log.persist(meta, dir=dir, matz=matz_entry)
        # the hot tail just spilled: drop the monolithic cache like any
        # other spill (persist bypasses the maybe_spill hook)
        self._packed = None
        return path

    @staticmethod
    def restore_tiered(dir: str, replica: Optional[int] = None,
                       use_matz: bool = True,
                       **tier_kw) -> "TpuTree":
        """Rebuild a tree from :meth:`checkpoint_tiered` output —
        O(tail) descriptor opens, no replay, no full column load (cold
        tiers page in lazily on first read).  When the manifest
        references a materialization artifact (and ``use_matz`` /
        ``GRAFT_MATZ`` allow), the FIRST READ also stays O(tail): the
        mirror loads from the artifact and replays only the ops past
        its coverage; a corrupt/stale/missing artifact falls back to
        the full merge with a :class:`MatzWarning` — never wrong
        data.  ``replica`` adopts a new identity exactly like
        :meth:`restore_packed`.  Raises
        :class:`~crdt_graph_tpu.core.errors.CheckpointError` (typed,
        never a silent partial log) on any missing or corrupt manifest
        or segment file."""
        from .codec import json_codec
        from .core.errors import CheckpointError
        from .oplog import OpLog, ViewSpanBatch
        if replica is not None:
            ts_mod.make(replica, 0)
        log, meta = OpLog.open_dir(dir, **tier_kw)
        try:
            rid_meta = meta["replica"]
            ts_mod.make(int(rid_meta), 0)
            max_depth = int(meta["max_depth"])
            if max_depth < 1:
                raise ValueError(f"max_depth {max_depth}")
            cursor = tuple(int(c) for c in meta["cursor"])
            replicas = {int(k): int(v)
                        for k, v in meta["replicas"].items()}
            timestamp = int(meta["timestamp"])
            last_op: Optional[Operation] = None
            span = meta.get("last_op_span")
            if span is not None:
                if not (isinstance(span, list) and len(span) == 2
                        and all(isinstance(x, int)
                                and not isinstance(x, bool)
                                for x in span)
                        and 0 <= span[0] <= span[1] <= len(log)):
                    raise ValueError(f"last_op_span {span!r} outside "
                                     f"the {len(log)}-op log")
            elif "last_operation" in meta:
                last_op = json_codec.decode(meta["last_operation"])
        except (KeyError, ValueError, TypeError, AttributeError) as e:
            raise CheckpointError(
                f"tiered checkpoint meta in {dir!r} invalid: "
                f"{type(e).__name__}: {e}") from e
        rid = rid_meta if replica is None else replica
        tree = TpuTree(rid, max_depth=max_depth)
        log._cfg.max_depth = max_depth
        tree._log = log
        log.set_on_spill(tree._on_log_spill)
        if use_matz and log.matz_entry is not None:
            tree._matz_pending = True
        tree._cursor = cursor
        tree._replicas = replicas
        if rid == rid_meta:
            tree._timestamp = timestamp
        else:
            tree._timestamp = max(ts_mod.make(rid, 0),
                                  replicas.get(rid, 0))
        # last_operation round-trips (ISSUE 9 satellite): a span
        # rebuilds LAZILY off the restored view (the span may be a
        # whole bootstrap ingest living in cold segments — restore
        # must stay O(tail)); a bare single op materializes eagerly so
        # the restored echo keeps the reference's bare-op shape; old
        # manifests without either key keep the empty-batch sentinel.
        if span is not None:
            s, e = span
            if e > s:
                vb = ViewSpanBatch(log.view(max_depth), s, e)
                if meta.get("last_op_bare") and e - s == 1:
                    tree._last_operation = vb.ops[0]
                else:
                    tree._last_operation = vb
        elif last_op is not None:
            tree._last_operation = last_op
        return tree


def packed_since_bytes(p: PackedOps, initial_timestamp: int) -> bytes:
    """Anti-entropy wire JSON (``GET /ops?since=``) straight off packed
    columns: the suffix from the Add matching ``initial_timestamp``
    (inclusive; 0 = full log; no match = empty batch — op_mod.since
    semantics), streamed through the native egress encoder
    (native/fastcodec.cpp ``encode_pack``) with a Python fallback for
    non-native-encodable payloads.  Single source of truth shared by
    the live tree (:meth:`TpuTree.dumps_since_bytes`) and the serving
    engine's published snapshots (serve/snapshot.py) — the applied log
    holds each add timestamp at most once, so the cached
    first-occurrence index IS the since() terminator and a delta pull
    costs O(1) after the first build."""
    from . import native
    from .codec import json_codec
    n = p.num_ops
    if initial_timestamp == 0:
        start = 0
    else:
        start = p.index().get(initial_timestamp)
        if start is None or start >= n:
            return b'{"op":"batch","ops":[]}'
    if native.available():
        try:
            return native.encode_pack(p, start)
        except ValueError:
            pass  # non-JSON-native payload: take the Python path
    return json_codec.dumps(op_mod.from_list(
        packed_mod.unpack_rows(p, start, n))).encode()


def packed_since_window(p: PackedOps, initial_timestamp: int,
                        limit: int = 0):
    """Bounded, resumable anti-entropy window over the packed log
    (``GET /ops?since=&limit=`` — cluster/antientropy.py).

    Returns ``(wire_bytes, meta)`` where ``meta`` is ``{"found",
    "more", "next_since", "count"}``:

    - ``found`` — whether the ``since`` terminator exists in this log.
      False means the serving replica does not know the Add the puller
      resumed from (e.g. it restarted with a fresh log); the puller
      must reset its high-water mark to 0 and re-pull (duplicates
      absorb), instead of spinning on empty batches forever.
    - ``more`` — rows remain past this window; the puller should
      resume immediately from ``next_since`` rather than waiting for
      its next round.
    - ``next_since`` — the timestamp of the last Add served (the
      resume point: ``operations_since`` terminators are Adds, so a
      window is trimmed — or, for a pathological all-delete stretch
      longer than ``limit``, extended — to END on an Add whenever rows
      remain).  None when the window served no Add (then the puller's
      existing mark still stands).
    - ``count`` — rows served.

    ``limit`` ≤ 0 serves the unbounded suffix (wire-compatible with
    :func:`packed_since_bytes`).  Every window is a plain wire batch —
    the reference codec never sees the windowing, which lives entirely
    in the HTTP headers (service/http.py)."""
    empty = b'{"op":"batch","ops":[]}'
    n = p.num_ops
    if initial_timestamp == 0:
        start = 0
    else:
        start = p.index().get(initial_timestamp)
        if start is None or start >= n:
            return empty, {"found": False, "more": False,
                           "next_since": None, "count": 0}
    if start >= n:
        return empty, {"found": True, "more": False,
                       "next_since": None, "count": 0}
    stop = n
    if 0 < limit < n - start:
        kinds = p.kind
        window_adds = np.nonzero(
            kinds[start:start + limit] == packed_mod.KIND_ADD)[0]
        # the window must contain an Add BEYOND the resume terminator
        # (row 0 of a resumed pull is the inclusive ``since`` Add
        # itself): trimming to it would hand back next_since == since
        # with more=1 and the chain would re-serve the same window
        # forever whenever a delete run ≥ limit follows the terminator
        if len(window_adds) and (initial_timestamp == 0
                                 or int(window_adds[-1]) > 0):
            # trim so the window ends on its last Add — the resume
            # terminator; the trailing deletes re-serve next window
            stop = start + int(window_adds[-1]) + 1
        else:
            # all-delete window (or only the re-served terminator):
            # extend through the next Add so the puller still gets a
            # NEW resume point (deletes cannot be ``since``
            # terminators)
            later = np.nonzero(
                kinds[start + limit:n] == packed_mod.KIND_ADD)[0]
            stop = start + limit + int(later[0]) + 1 if len(later) \
                else n
        if stop < n and not np.any(
                kinds[stop:n] == packed_mod.KIND_ADD):
            # everything past the trimmed window is deletes: serve the
            # tail NOW (there is no later Add to carry it, so "re-serve
            # next window" would chain forever on the same terminator
            # and the final deletes would never replicate)
            stop = n
    if stop >= n:
        body = packed_since_bytes(p, initial_timestamp)
        stop = n
    else:
        sub = packed_mod.select_rows(p, np.arange(start, stop))
        body = packed_since_bytes(sub, 0)
    served_adds = np.nonzero(
        p.kind[start:stop] == packed_mod.KIND_ADD)[0]
    next_since = int(p.ts[start + int(served_adds[-1])]) \
        if len(served_adds) else None
    return body, {"found": True, "more": stop < n,
                  "next_since": next_since, "count": stop - start}


def write_packed_npz(path, p: PackedOps, meta: dict,
                     compress: bool = True) -> None:
    """Write the packed-checkpoint npz wire/disk format: ``p``'s real
    rows (capacity padding never hits the wire — restore re-pads to the
    jit bucket) plus a JSON ``meta`` sidecar.  Single source of truth
    for the format, shared by :meth:`TpuTree.checkpoint_packed` and the
    serving engine's snapshot endpoint (serve/snapshot.py), which
    builds its meta from a published immutable snapshot instead of a
    live tree.  ``path`` may be a filesystem path or a binary
    file-like."""
    import json
    f = path if hasattr(path, "write") else open(path, "wb")
    n = p.num_ops
    try:
        (np.savez_compressed if compress else np.savez)(
            f, kind=p.kind[:n], ts=p.ts[:n],
            parent_ts=p.parent_ts[:n],
            anchor_ts=p.anchor_ts[:n], depth=p.depth[:n],
            paths=p.paths[:n], value_ref=p.value_ref[:n],
            pos=p.pos[:n], parent_pos=p.parent_pos[:n],
            anchor_pos=p.anchor_pos[:n], target_pos=p.target_pos[:n],
            ts_rank=p.ts_rank[:n],
            values=np.frombuffer(json.dumps(p.values).encode(),
                                 np.uint8),
            meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    finally:
        if f is not path:
            f.close()


def init(replica: int, max_depth: int = DEFAULT_MAX_DEPTH) -> TpuTree:
    """Build a TPU-engine replica (API parity with core.tree.init)."""
    return TpuTree(replica, max_depth=max_depth)


restore = TpuTree.restore
