"""Ops-axis sharded merge: the single-document kernel at M/k width per
device with ring-collective boundary exchange.

This is the one path that shards ONE document's merge over several
devices; the docs axis (independent documents,
``mesh.batched_materialize``, docs/SHARD_TAIL.md §6) is the other
multi-device axis.  It shards the kernel's billed memory ops — tour
scan, plane sweeps, rank expansion, order scatters — following the §4
design of docs/SHARD_TAIL.md:

- **One shard_map, one code path.**  The whole kernel runs inside ONE
  ``shard_map`` over a 1-D ``ops`` mesh; the body all-gathers the op
  columns once (the boundary exchange) and then calls the STOCK
  kernel — ``merge._materialize`` — with a :class:`OpsAxisPart`
  partition context threaded through it.  Every
  stage the context does not intercept runs replicated and is therefore
  bit-identical by construction; the intercepted stages are proven-
  equal rewrites (associative scan splits, disjoint-index scatter
  joins, windowed gathers), pinned across all 8 sweep shapes by
  tests/test_opsaxis.py.
- **Tour-scan prefix = local scan + ring carries + local fixup**
  (ops/tour_scan.sharded_prefix_sums): each device cumsums its
  contiguous ceil(M/k)-wide chunks; per-chunk run-id offsets and
  suffix-weight totals ride one fused ``lax.ppermute`` ring; a local
  elementwise fixup finishes.  Exact — integer addition is associative.
- **Bounded-span plane sweeps get halo rows**: the node-frame plane
  gather and the parent-plane gather read, per device, only a
  ``[W + 2·HALO]``-row window around its own slot range (HALO is
  STATIC — fused_resolve's span bound, SPAN2 = SPAN + 2·HOP_J, already
  bounds how far a vouched batch's source rows stray from the
  diagonal).  ROOT/NULL rows are overlaid elementwise (their frames
  are constants of the construction).  A batch whose indices straddle
  more than the halo fails the replicated window check and the WHOLE
  gather falls back to the single-device path via ``lax.cond`` —
  exactly the existing lax-fallback pattern; fallback speed, never
  correctness.
- **Frame scatters join like semilattices**: order/visible-order/
  first-child scatters write globally-unique targets, so each device
  scatters only its ceil(M/k) local pairs into a default frame and one
  ``lax.pmin``/``pmax`` joins the frames; scatter-adds join by
  ``psum``.

On the 8-device host-platform CPU mesh every collective executes for
real (lax.ppermute/psum/all_gather — tier-1 runs this path), and the
body compiles for a described v5e:2x2 mesh (tests/test_tpu_compile.py).

What stays replicated per device, disclosed: all ELEMENTWISE M-wide
arithmetic (the cost model bills memory ops, not elementwise lanes —
docs/TPU_PROFILE.md §3), the compact sub-threshold stages (S_CAP/R_CAP
sibling sort and Wyllie — §4 items 4+6, the Amdahl core), and the
0-trip fixpoint loop bodies.  utils/chainaudit.py v3 audits the traced
shard body and CI pins: no billed fast-path op inside it wider than
ceil(M/k) + HALO at 1M config 5, and the collective bytes within the
documented bound (tests/test_chain_audit.py).

Serving routes big merges here behind the ``GRAFT_OPSAXIS``
kill-switch: candidate sets ≥ ``GRAFT_OPSAXIS_MIN_OPS`` (default 256k)
on hosts with ≥ 2 devices whose padded capacity the device count
divides (engine.py) — the NodeTable shapes are then identical to the
single-device path, so chunked-apply rollback and
``last_applied_mask`` attribution ride through unchanged; fingerprints
and sync windows are pinned byte-identical flag on/off.  The sharded
executable is ``jit__opsaxis_materialize`` in a profile (a merge, by
name, to every reader that looks for ``materialize``); placing its
columns on the mesh is the ``opsaxis.place`` span.  The route keeps one
process-wide tally, counted in :func:`routed_materialize`: the serving
engine's counters show it as ``opsaxis_merges``, ``opsaxis_rows`` (the
bucket rows of routed merges), ``kernel_rows`` (the bucket rows of
every single-document kernel merge, routed or not) and
``kernel_delete_rows`` (those of its rows that are Deletes), and
:func:`stats` and the ``crdt_opsaxis_*`` families read the same counts.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..codec import packed as packed_mod
from ..ops import fused_resolve, merge as merge_mod
from ..ops import tour_scan
from ..ops.merge import NodeTable
from ..utils import hostenv, profiling

AXIS = "ops"

# Static halo rows per shard edge for the windowed plane sweeps: the
# 2-hop span bound the pallas sweeps already enforce (fused_resolve
# SPAN2 = SPAN + 2·HOP_J) — a vouched near-diagonal batch's source rows
# stay within it, and anything that strays takes the single-device
# fallback exactly like the pallas span check does.
HALO = fused_resolve.SPAN2

# documented collective-byte bound for the 1M config-5 trace (CI gate,
# tests/test_chain_audit.py): the input-column exchange (~56 B/op) +
# the replicated-output reassembly all-gathers (plane rows, prefix
# lanes, rank frames ≈ 190 B/op) with ~25% headroom.  Billed as summed
# collective OUTPUT bytes per device (chainaudit v3 counting rule).
COLLECTIVE_BYTES_CAP_1M = 320 * 1024 * 1024


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class OpsAxisPart:
    """Partition context threaded through ``merge._finish``: the
    sharded implementations of the kernel's billed memory ops (module
    docstring).  Lives only inside the shard_map body; every method
    takes replicated operands and returns replicated results."""

    def __init__(self, k: int):
        self.k = k
        self.halo = HALO

    # -- local slicing helpers -------------------------------------------

    def _w(self, n: int) -> int:
        return _ceil_div(n, self.k)

    def _local(self, arr: jax.Array, w: int) -> jax.Array:
        """Device's own contiguous chunk of a replicated 1-D array,
        padded so every device slices a full ``w`` rows."""
        i = lax.axis_index(AXIS)
        pad = self.k * w - arr.shape[0]
        if pad:
            arr = jnp.pad(arr, [(0, pad)] + [(0, 0)] * (arr.ndim - 1))
        start = (i * w,) + (jnp.zeros((), i.dtype),) * (arr.ndim - 1)
        return lax.dynamic_slice(arr, start,
                                 (w,) + arr.shape[1:])

    def _ag(self, local: jax.Array, n: int) -> jax.Array:
        """Tiled all-gather of per-device chunks back to the replicated
        [n]-row array."""
        return lax.all_gather(local, AXIS, tiled=True)[:n]

    # -- halo-windowed plane row gather ----------------------------------

    def plane_rows(self, plane: jax.Array, idx: jax.Array) -> jax.Array:
        """``plane[idx]`` with each device gathering only its own
        ceil(M/k) output rows from a static [W + 2·HALO]-row halo
        window around its slot range.  ROOT (row 0) and NULL (last
        row) reads are overlaid elementwise — both rows are constants
        of the frame construction — so the common cross-shard
        references (root parents, parked slots) never widen the halo.
        A batch whose remaining indices straddle the window falls back
        wholesale to the single-device gather via ``lax.cond`` (the
        replicated predicate keeps every device on the same branch)."""
        r, c = plane.shape
        mi = idx.shape[0]
        w = self._w(mi)
        wwin = w + 2 * self.halo
        # replicated window check (fused_resolve.halo_window_ok — the
        # ops-axis twin of the pallas sweeps' per-tile span checks)
        ok = fused_resolve.halo_window_ok(idx, w, self.halo, r)

        def _windowed(_):
            i = lax.axis_index(AXIS)
            lo = i * w
            plane_p = plane
            if r < wwin:
                plane_p = jnp.pad(plane, ((0, wwin - r), (0, 0)))
            rp = plane_p.shape[0]
            start = jnp.clip(lo - self.halo, 0, rp - wwin)
            win = lax.dynamic_slice(
                plane_p, (start, jnp.zeros((), start.dtype)), (wwin, c))
            idx_l = self._local(idx, w)
            off = jnp.clip(idx_l - start, 0, wwin - 1)
            g = win[off]
            g = jnp.where((idx_l <= 0)[:, None], plane[0][None, :], g)
            g = jnp.where((idx_l >= r - 1)[:, None],
                          plane[r - 1][None, :], g)
            return self._ag(g, mi)

        return lax.cond(ok, _windowed, lambda _: plane[idx], None)

    # -- per-row gathers from replicated frames --------------------------

    def gather_rows(self, table: jax.Array, idx: jax.Array) -> jax.Array:
        """``table[idx]`` with the INDEX axis sharded: each device
        gathers its own ceil(len/k) rows, one tiled all-gather
        reassembles.  ``table`` may be 1-D or a [rows, C] plane."""
        n = idx.shape[0]
        w = self._w(n)
        idx_l = self._local(idx, w)
        return self._ag(table[idx_l], n)

    # -- frame scatters joined by all-reduce -----------------------------

    def frame_set(self, size: int, default, tgt: jax.Array,
                  val: jax.Array, combine: str,
                  dtype=jnp.int32) -> jax.Array:
        """``full(size, default).at[tgt].set(val, mode="drop")`` with
        the scatter's INDEX axis sharded and the per-device frames
        joined by ``pmin``/``pmax`` — exact when targets are globally
        unique and every scattered value wins ``default`` under the
        combine."""
        n = tgt.shape[0]
        w = self._w(n)
        # pad targets with ``size`` (dropped) so pad rows scatter nowhere
        i = lax.axis_index(AXIS)
        pad = self.k * w - n
        tgt_p = jnp.pad(tgt, (0, pad), constant_values=size) if pad \
            else tgt
        val_p = jnp.pad(val, (0, pad)) if pad else val
        tgt_l = lax.dynamic_slice(tgt_p, (i * w,), (w,))
        val_l = lax.dynamic_slice(val_p, (i * w,), (w,))
        frame = jnp.full(size, default, dtype).at[tgt_l].set(
            val_l.astype(dtype), mode="drop", unique_indices=True)
        red = lax.pmin if combine == "min" else lax.pmax
        return red(frame, AXIS)

    def frame_reduce(self, size: int, default, tgt: jax.Array,
                     val: jax.Array, op: str) -> jax.Array:
        """``full(size, default).at[tgt].min/max(val, mode="drop")``
        with DUPLICATE targets allowed (winner election, delete
        tombstones): per-device partial reduce frames joined by
        ``pmin``/``pmax`` — exact because min/max are associative,
        commutative, and absorb the default identity."""
        n = tgt.shape[0]
        w = self._w(n)
        i = lax.axis_index(AXIS)
        pad = self.k * w - n
        tgt_p = jnp.pad(tgt, (0, pad), constant_values=size) if pad \
            else tgt
        val_p = jnp.pad(val, (0, pad)) if pad else val
        tgt_l = lax.dynamic_slice(tgt_p, (i * w,), (w,))
        val_l = lax.dynamic_slice(val_p, (i * w,), (w,))
        frame = jnp.full(size, default, val.dtype)
        if op == "min":
            frame = frame.at[tgt_l].min(val_l, mode="drop")
            return lax.pmin(frame, AXIS)
        frame = frame.at[tgt_l].max(val_l, mode="drop")
        return lax.pmax(frame, AXIS)

    def frame_add(self, size: int, tgt: jax.Array,
                  val=1) -> jax.Array:
        """``zeros(size).at[tgt].add(val, mode="drop")`` sharded along
        the index axis, per-device partial counts joined by ``psum``
        (exact: integer addition)."""
        n = tgt.shape[0]
        w = self._w(n)
        tgt_l = self._local(jnp.where(tgt >= size, size, tgt), w)
        # pad rows beyond n must not count: _local pads with 0, which
        # WOULD land in the frame — re-mask by global row position
        i = lax.axis_index(AXIS)
        rows = i * w + jnp.arange(w, dtype=jnp.int32)
        tgt_l = jnp.where(rows < n, tgt_l, size)
        frame = jnp.zeros(size, jnp.int32).at[tgt_l].add(
            val, mode="drop")
        return lax.psum(frame, AXIS)

    # -- chunked scans with ring carries ---------------------------------

    def prefix_sums(self, boundary: jax.Array, weights: jax.Array):
        """The tour-scan prefix (run-id cumsum over T tokens + weight
        lanes over M slots): local chunk scans + one fused ppermute
        ring of the carries + local fixup
        (ops/tour_scan.sharded_prefix_sums)."""
        return tour_scan.sharded_prefix_sums(boundary, weights,
                                             axis=AXIS, k=self.k)

    def cumsum(self, x: jax.Array) -> jax.Array:
        """1-D inclusive integer cumsum, chunked with ring carries."""
        n = x.shape[0]
        w = self._w(n)
        loc = lax.cumsum(self._local(x.astype(jnp.int32), w))
        carry = tour_scan.ring_exclusive(loc[-1:], AXIS, self.k)
        return self._ag(loc + carry[0], n)

    def cummax(self, x: jax.Array) -> jax.Array:
        """1-D inclusive integer cummax, chunked with ring MAX carries.
        Values are biased non-negative so ppermute's zero-fill acts as
        the identity (tour_scan.ring_exclusive op="max" contract)."""
        n = x.shape[0]
        w = self._w(n)
        lo = jnp.min(x)
        bias = jnp.maximum(jnp.int32(1) - lo, 0)
        loc = lax.cummax(self._local(x, w) + bias)
        # pad rows (value 0 + bias) could inflate the carry of the LAST
        # chunk only — re-mask pad rows to the identity
        i = lax.axis_index(AXIS)
        rows = i * w + jnp.arange(w, dtype=jnp.int32)
        loc = jnp.where(rows < n, loc, 0)
        carry = tour_scan.ring_exclusive(loc[-1:], AXIS, self.k,
                                         op="max")
        fixed = jnp.maximum(loc, carry[0]) - bias
        return self._ag(fixed, n)

    def mono_expand(self, per_run: jax.Array,
                    rid_m: jax.Array) -> jax.Array:
        """``per_run[:, rid_m]`` (the rank-expansion monotone gather)
        with the token axis sharded."""
        n = rid_m.shape[0]
        w = self._w(n)
        rid_l = self._local(rid_m, w)
        g = per_run[:, rid_l]                       # [rows, W] gather
        return jnp.swapaxes(self._ag(jnp.swapaxes(g, 0, 1), n), 0, 1)


# ---- the shard_map entry ------------------------------------------------

# every op column crosses sharded; order fixed for the jit signature
def _cols_of(ops: Dict[str, np.ndarray]):
    return tuple(sorted(ops.keys()))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "cols", "hints",
                                    "no_deletes"))
def _opsaxis_materialize(device_ops, mesh: Mesh, cols, hints,
                         no_deletes: bool) -> NodeTable:
    k = mesh.shape[AXIS]

    def body(*vals):
        # boundary exchange: the op columns all-gather ONCE (each
        # device owns a contiguous ops shard on entry), then the
        # STOCK kernel runs with the partition context intercepting
        # its billed memory ops.  use_pallas pinned False: Mosaic
        # calls must not trace inside shard_map (mesh.py precedent).
        gathered = {c: lax.all_gather(v, AXIS, tiled=True)
                    for c, v in zip(cols, vals)}
        part = OpsAxisPart(k)
        return merge_mod._materialize.__wrapped__(
            gathered, False, hints, no_deletes, part=part)

    spec = tuple(P(AXIS) if device_ops[c].ndim == 1 else P(AXIS, None)
                 for c in cols)
    fn = jax.shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=P(), check_vma=False)
    return fn(*[device_ops[c] for c in cols])


_MESHES: Dict[int, Mesh] = {}
_STATS = {"merges": 0, "devices": 0, "routed_ops": 0, "kernel_rows": 0,
          "kernel_delete_rows": 0}
_STATS_LOCK = threading.Lock()


def _mesh(k: int) -> Mesh:
    m = _MESHES.get(k)
    if m is None:
        m = _MESHES[k] = Mesh(np.asarray(jax.devices()[:k]), (AXIS,))
    return m


def mesh_devices() -> int:
    """The ops-axis width this host would shard at: the largest power
    of two ≤ the local device count."""
    n = len(jax.devices())
    return 1 << (n.bit_length() - 1) if n else 1


def materialize(ops: Dict[str, np.ndarray], k: Optional[int] = None,
                hints: str = "auto") -> NodeTable:
    """One ops-axis sharded merge: bit-identical to
    ``merge.materialize`` on the same (padded) arrays.  ``k`` defaults
    to :func:`mesh_devices`; a non-divisible op count pads to the next
    multiple (the padded tail rides the LAST shard), which widens the
    returned table exactly like padding before the stock kernel would.
    """
    if k is None:
        k = mesh_devices()
    n = ops["kind"].shape[0]
    n_pad = _ceil_div(n, k) * k
    padded = packed_mod.pad_arrays(ops, n_pad) if n_pad != n else ops
    no_deletes = merge_mod.host_no_deletes(np.asarray(padded["kind"]))
    cols = _cols_of(padded)
    mesh = _mesh(k)

    def run():
        with profiling.span("opsaxis.place", rows=n_pad, k=k):
            device_ops = {
                c: jax.device_put(
                    padded[c],
                    NamedSharding(mesh, P(AXIS) if padded[c].ndim == 1
                                  else P(AXIS, None)))
                for c in cols}
        return _opsaxis_materialize(device_ops, mesh, cols, hints,
                                    no_deletes)

    with _STATS_LOCK:
        _STATS["devices"] = k
        # shape signature of the last routed merge, kept so runtime
        # reporters (bench/loadgen) can re-derive the shard audit
        # without holding the arrays (shape-only tracing)
        _STATS["last"] = {
            "k": k, "hints": hints, "no_deletes": no_deletes,
            "shapes": {c: (tuple(np.asarray(padded[c]).shape),
                           str(np.asarray(padded[c]).dtype))
                       for c in cols},
            "leg": "hinted" if merge_mod.crowding_hinted(
                padded, hints, no_deletes) else "counted",
        }
    if jax.config.jax_enable_x64:
        return run()
    with jax.enable_x64(True):
        return run()


# ---- serving route (engine.py) ------------------------------------------

MIN_OPS_DEFAULT = 1 << 18


def route_min_ops() -> int:
    return hostenv.env_int("GRAFT_OPSAXIS_MIN_OPS", MIN_OPS_DEFAULT)


def enabled_for(n_ops: int) -> bool:
    """True when a candidate set of ``n_ops`` rows should take the
    sharded path: GRAFT_OPSAXIS on (kill-switch, default on), ≥ 2
    devices (so <2-device hosts default off), the batch at or past the
    route threshold, and the capacity divisible by the mesh width (the
    engine's power-of-two buckets always are — divisibility keeps the
    NodeTable shapes identical to the single-device path, which the
    serving rollback/attribution contract relies on)."""
    if not hostenv.flag_on("GRAFT_OPSAXIS"):
        return False
    k = mesh_devices()
    return k >= 2 and n_ops >= route_min_ops() and n_ops % k == 0


def routed_materialize(arrays: Dict[str, np.ndarray],
                       hints) -> NodeTable:
    """The serving dispatch: ``merge.materialize`` or the sharded path
    per :func:`enabled_for` — same arrays, same hints mode, identical
    table either way (pinned by tests/test_opsaxis.py through the
    serving path)."""
    n = int(arrays["kind"].shape[0])
    n_del = int(np.count_nonzero(
        np.asarray(arrays["kind"]) == packed_mod.KIND_DELETE))
    routed = enabled_for(n)
    with _STATS_LOCK:
        _STATS["kernel_rows"] += n
        _STATS["kernel_delete_rows"] += n_del
        if routed:
            _STATS["merges"] += 1
            _STATS["routed_ops"] += n
    if routed:
        return materialize(arrays, hints=hints)
    return merge_mod.materialize(arrays, hints=hints)


def route_counts() -> Dict[str, int]:
    """The route's tally under the names the serving engine's counters
    give it (module docstring)."""
    with _STATS_LOCK:
        return {"opsaxis_merges": _STATS["merges"],
                "opsaxis_rows": _STATS["routed_ops"],
                "kernel_rows": _STATS["kernel_rows"],
                "kernel_delete_rows": _STATS["kernel_delete_rows"]}


def stats() -> dict:
    """Routing counters for the prom scrape + scheduler metrics."""
    with _STATS_LOCK:
        out = {k: _STATS[k] for k in ("merges", "devices", "routed_ops")}
    out["enabled"] = hostenv.flag_on("GRAFT_OPSAXIS")
    out["min_ops"] = route_min_ops()
    out["halo_rows"] = HALO
    return out


# ---- audit (chainaudit v3 wiring) ---------------------------------------

def _audit_traced(shapes: Dict[str, jax.ShapeDtypeStruct], k: int,
                  hints, no_deletes: bool, leg: str) -> dict:
    """The shared core: trace the shard_map program shape-only, bill
    per-shard widths + collective bytes (utils/chainaudit.py v3), and
    shape the bench-facing ``opsaxis`` record."""
    from ..utils import chainaudit
    cols = tuple(sorted(shapes))
    mesh = _mesh(k)

    def fn(device_ops):
        return _opsaxis_materialize.__wrapped__(device_ops, mesh, cols,
                                                hints, no_deletes)

    with jax.enable_x64(True):
        audit = chainaudit.count_mwide(fn, shapes)
    m = shapes["kind"].shape[0] + 2
    budget = _ceil_div(m, k) + HALO
    return {
        "devices": k,
        "shard_width": audit.shard_width,
        "shard_budget": budget,
        "halo_rows": HALO,
        "collective_bytes": audit.collective_bytes,
        "collective_count": audit.collective_count,
        "leg": leg,
        "ok": bool(audit.shard_width <= budget),
    }


def audit_opsaxis(ops: Dict[str, np.ndarray], k: Optional[int] = None,
                  hints: str = "exhaustive") -> dict:
    """Shape-only audit of the sharded trace for an op-column dict:
    the ``opsaxis`` stats record {devices, shard_width, shard_budget,
    halo_rows, collective_bytes, leg, ok} every bench row carries
    (bench/runner.py)."""
    if k is None:
        k = mesh_devices()
    n = ops["kind"].shape[0]
    n_pad = _ceil_div(n, k) * k
    padded = packed_mod.pad_arrays(ops, n_pad) if n_pad != n else ops
    no_deletes = merge_mod.host_no_deletes(np.asarray(padded["kind"]))
    leg = "hinted" if merge_mod.crowding_hinted(padded, hints,
                                                no_deletes) \
        else "counted"
    shapes = {c: jax.ShapeDtypeStruct(np.asarray(padded[c]).shape,
                                      np.asarray(padded[c]).dtype)
              for c in _cols_of(padded)}
    return _audit_traced(shapes, k, hints, no_deletes, leg)


def audit_last() -> Optional[dict]:
    """The shard audit of the LAST routed merge's shape signature
    (recorded by :func:`materialize`) — what the loadgen report
    attaches without ever holding the arrays.  None when nothing
    routed this process."""
    with _STATS_LOCK:
        last = _STATS.get("last")
    if not last:
        return None
    shapes = {c: jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt))
              for c, (shape, dt) in last["shapes"].items()}
    return _audit_traced(shapes, last["k"], last["hints"],
                         last["no_deletes"], last["leg"])
