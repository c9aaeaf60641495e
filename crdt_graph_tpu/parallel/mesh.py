"""Multi-chip scale-out for the merge kernel: meshes, shardings, collectives.

The reference distributes by shipping JSON op batches between replicas over
an application-provided network (CRDTree/Operation.elm:109-159, README.md:
20-22).  Here "the network" is the TPU interconnect: op arrays live sharded
over a ``jax.sharding.Mesh`` and the collectives XLA inserts for the merge
kernel's sorts and gathers ride ICI/DCN.

Two orthogonal mesh axes, composable into a 2-D mesh:

- ``docs`` — data parallelism over independent documents (trees).  A server
  merging many documents batches them on a leading axis and shards that axis;
  merges never communicate across documents, so scaling is linear.  This is
  the realistic serving axis (each collaborative document is independent).
- ``ops`` — the packed op axis within one document.  Here it is the
  optional second axis of :func:`batched_materialize`; ONE document's
  merge sharded over the op axis is :mod:`crdt_graph_tpu.parallel.opsaxis`
  (a ``shard_map`` body with hand-placed collectives, the served
  single-document multi-device path).

Entry points:

- :func:`make_mesh` — build a 1-D or 2-D device mesh.
- :func:`batched_materialize` — B independent merges, vmapped on a leading
  doc axis, sharded over ``docs`` (and optionally ``ops``).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..codec.packed import PackedOps
from ..codec.packed import pad_arrays as packed_pad_arrays
from ..ops import merge as merge_mod
from ..ops.merge import NodeTable

DOCS_AXIS = "docs"
OPS_AXIS = "ops"


def make_mesh(n_docs: int = 1, n_ops: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A ``(docs, ops)`` mesh over ``n_docs * n_ops`` devices."""
    if devices is None:
        devices = jax.devices()
    need = n_docs * n_ops
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_docs, n_ops)
    return Mesh(grid, (DOCS_AXIS, OPS_AXIS))


# canonical implementation lives with the column format (codec.packed);
# kept under the old name for the existing call sites
_pad_ops_to = packed_pad_arrays


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _materialize_batched_safe(ops):
    # default batched body: the hinted path's lax.cond would execute
    # BOTH branches under vmap, so hints are dropped and the join runs;
    # pallas stays off (must not trace under vmap)
    ops = {k: v for k, v in ops.items()
           if k not in ("parent_pos", "anchor_pos", "target_pos")}
    return merge_mod._materialize.__wrapped__(ops, False, "join")


def _materialize_batched_exhaustive(ops):
    # opt-in fast body (batched_materialize(exhaustive_hints=True)):
    # cond-free hinted resolution, valid ONLY for batches whose hint
    # coverage the caller vouches for (pack/stack_packed provenance) —
    # a violated promise silently mis-resolves references
    return merge_mod._materialize.__wrapped__(ops, False, "exhaustive")


_batched_kernel = jax.jit(jax.vmap(_materialize_batched_safe))
_batched_kernel_hinted = jax.jit(jax.vmap(_materialize_batched_exhaustive))


def batched_materialize(ops: Dict[str, np.ndarray], mesh: Mesh,
                        shard_ops_axis: bool = False,
                        exhaustive_hints: bool = False) -> NodeTable:
    """B independent merges: arrays carry a leading document axis ``[B, N]``.

    The doc axis is sharded over ``docs`` — embarrassingly parallel, linear
    scaling (the serving path: many documents, one merge each).  With
    ``shard_ops_axis`` the op axis is additionally sharded over ``ops`` for
    2-D parallelism on large per-document batches.

    ``exhaustive_hints=True`` opts into the cond-free hinted timestamp
    resolution — ONLY for batches whose link-hint coverage the caller
    vouches for (pack/stack_packed provenance); the default drops hints
    and joins, which is correct for any input.
    """
    n_docs = mesh.shape[DOCS_AXIS]
    b = ops["kind"].shape[0]
    if b % n_docs != 0:
        raise ValueError(f"doc axis {b} not divisible by mesh docs axis "
                         f"{n_docs}; pad the document batch")
    op_spec = (OPS_AXIS,) if shard_ops_axis else (None,)

    def spec_for(v: np.ndarray) -> P:
        return P(DOCS_AXIS, *op_spec[:max(0, v.ndim - 1)])

    kernel = _batched_kernel_hinted if exhaustive_hints else _batched_kernel

    def _placed(v, sharding):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            # cross-process global array (host_local_docs_to_global
            # already placed it): a device_put here would RESHARD
            # through the data plane, which the CPU multi-controller
            # backend cannot do — the computation follows the array's
            # existing docs-axis sharding instead
            return v
        return jax.device_put(v, sharding)

    def run():
        device_ops = {k: _placed(v, NamedSharding(mesh, spec_for(v)))
                      for k, v in ops.items()}
        return kernel(device_ops)

    if jax.config.jax_enable_x64:
        return run()
    with jax.enable_x64(True):
        return run()


def stack_aligned(batches: Sequence[PackedOps]
                  ) -> "tuple[Dict[str, np.ndarray], list]":
    """:func:`stack_packed` plus per-document capacity alignment: every
    batch is first re-padded to the SHARED capacity (codec.packed
    ``with_capacity``) and the aligned PackedOps are returned alongside
    the stacked arrays.  The serving scheduler commits each document
    against its slice of the batched table, so the per-document columns
    it parks must have the same row capacity the table was materialized
    at — stacking alone would leave them inconsistent."""
    from ..codec.packed import with_capacity
    shared = max(p.capacity for p in batches)
    aligned = [with_capacity(p, shared) for p in batches]
    return stack_packed(aligned), aligned


class LingerBatcher:
    """Cross-caller batch accumulation window for the vmapped launch
    (the merge tier's coalescing heart — mergetier/worker.py).

    Many threads each hold ONE item (a document's prepared candidate
    set) and want it materialized; a wide ``batched_materialize``
    amortizes launch overhead across all of them, but only if their
    arrivals meet in the same launch.  :meth:`submit` parks the caller
    while a shared window fills: the FIRST arrival becomes the epoch's
    leader and waits up to ``linger_s`` (``GRAFT_MERGETIER_BATCH_MS``)
    for co-travellers, launching early the moment ``max_width`` items
    are aboard; everyone else rides, and every submitter gets exactly
    its own item's result back.  A failed launch fails the WHOLE
    epoch's submitters (each caller falls back on its own — for the
    merge tier that is the front-end's bit-identical local merge).

    ``launch`` receives the epoch's item list and must return one
    result per item, in order.  It runs on the leader's thread; the
    batcher itself never touches JAX, so the one-thread-owns-JAX
    serving invariant is the launch callable's business, not ours.
    """

    def __init__(self, launch: Callable[[List[Any]], List[Any]],
                 linger_s: float = 0.002, max_width: int = 16):
        self._launch = launch
        self.linger_s = max(0.0, float(linger_s))
        self.max_width = max(1, int(max_width))
        self._cv = threading.Condition()
        self._epoch = 0
        self._items: List[Any] = []          # current epoch's cargo
        self._done: Dict[int, tuple] = {}    # epoch -> (results, error)
        self._riders: Dict[int, int] = {}    # epoch -> riders not yet woken
        self._closed = False
        # telemetry (read under the cv by stats())
        self.launches = 0
        self.items_in = 0
        self.full_launches = 0               # width cap hit (no linger)
        self.linger_waits = 0                # epochs that waited the window

    def submit(self, item: Any) -> Any:
        """Park until this item's epoch launches; returns its result.
        Raises whatever the epoch's launch raised (every rider sees the
        same error) or ``RuntimeError`` after :meth:`close`."""
        with self._cv:
            if self._closed:
                raise RuntimeError("batcher closed")
            epoch = self._epoch
            self._items.append(item)
            self.items_in += 1
            index = len(self._items) - 1
            leader = index == 0
            if not leader and len(self._items) >= self.max_width:
                # cap reached: wake the lingering leader early
                self._cv.notify_all()
            if leader:
                deadline = time.monotonic() + self.linger_s
                waited = False
                while (len(self._items) < self.max_width
                       and not self._closed):
                    remain = deadline - time.monotonic()
                    if remain <= 0:
                        break
                    waited = True
                    self._cv.wait(remain)
                cargo, self._items = self._items, []
                self._epoch += 1
                if waited:
                    self.linger_waits += 1
                if len(cargo) >= self.max_width:
                    self.full_launches += 1
            else:
                while epoch not in self._done and not self._closed:
                    self._cv.wait(1.0)
                if epoch not in self._done:
                    raise RuntimeError("batcher closed mid-epoch")
                results, error = self._done[epoch]
                # last rider out sweeps the epoch's parking spot
                self._rider_done(epoch)
                if error is not None:
                    raise error
                return results[index]
        # leader, outside the lock: run the launch for the whole epoch
        results: Optional[List[Any]] = None
        error: Optional[BaseException] = None
        try:
            results = self._launch(cargo)
            if results is None or len(results) != len(cargo):
                raise RuntimeError(
                    f"launch returned {0 if results is None else len(results)}"
                    f" results for {len(cargo)} items")
        except BaseException as e:   # noqa: BLE001 — every rider must
            # wake with THIS error, whatever class it is
            error = e
        with self._cv:
            self.launches += 1
            self._done[epoch] = (results, error)
            self._riders[epoch] = len(cargo) - 1
            if self._riders[epoch] == 0:
                del self._done[epoch], self._riders[epoch]
            self._cv.notify_all()
        if error is not None:
            raise error
        return results[0]

    def _rider_done(self, epoch: int) -> None:
        # requires self._cv
        self._riders[epoch] -= 1
        if self._riders[epoch] == 0:
            del self._done[epoch], self._riders[epoch]

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def stats(self) -> Dict[str, Any]:
        with self._cv:
            return {"launches": self.launches,
                    "items_in": self.items_in,
                    "full_launches": self.full_launches,
                    "linger_waits": self.linger_waits,
                    "linger_ms": round(self.linger_s * 1e3, 3),
                    "max_width": self.max_width,
                    "pending": len(self._items)}


def stack_packed(batches: Sequence[PackedOps]) -> Dict[str, np.ndarray]:
    """Stack per-document packed ops into ``[B, N]`` arrays (N = max,
    pad-extended; path planes widened to the widest depth bucket) for
    :func:`batched_materialize`."""
    n = max(p.capacity for p in batches)
    width = max(p.max_depth for p in batches)
    per = []
    for p in batches:
        arrs = dict(p.arrays())
        if arrs["paths"].shape[1] < width:
            wide = np.zeros((arrs["paths"].shape[0], width),
                            dtype=arrs["paths"].dtype)
            wide[:, :arrs["paths"].shape[1]] = arrs["paths"]
            arrs["paths"] = wide
        per.append(_pad_ops_to(arrs, n))
    # derived slot-hint columns ride along only when EVERY document has
    # them (arrays() omits them for unvouched batches; a mixed stack
    # takes the gather-based resolution rather than trusting half)
    keys = set(per[0])
    for d in per[1:]:
        keys &= set(d)
    return {k: np.stack([d[k] for d in per]) for k in keys}
