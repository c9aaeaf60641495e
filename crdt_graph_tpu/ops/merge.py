"""The batched semilattice join: N operations → converged node table, jitted.

This kernel replaces the reference's sequential merge — a left fold of
single-op tree edits, O(ops × depth × siblings)
(CRDTree.elm:224-232, 408-418) — with one data-parallel pass whose depth is
O(log N) pointer-doubling steps.  It treats the operation batch as an
unordered SET: applying it is a semilattice join, so merging replicas is
just concatenating their op arrays and materialising.  Idempotence,
commutativity and convergence hold by construction.

The central idea: **RGA document order is the DFS pre-order of an "order
forest"** derived from the ops alone.

Getting this forest right is subtle — the sequential skip-scan (insert after
the anchor, walking right past siblings with larger timestamps,
Internal/Node.elm:93-104) does NOT yield the naive anchor-forest DFS: a
low-timestamp insert can come to rest deep inside another anchor's subtree
(RGA's well-known interleaving behaviour).  The converged order it does
yield is the *greedy max-timestamp linearisation* of the anchor forest —
repeatedly emit the largest-timestamp node whose anchor has already been
emitted — which is equivalent to the DFS pre-order of the **min-ancestor
tree** T*:

- Within a branch, each node's T* parent is the NEAREST node on its anchor
  chain with a SMALLER timestamp (chain exhausted → the branch head).
- T* children sort timestamp-DESCENDING; T* chains are timestamp-increasing
  downward.

Why: whether x is emitted before y is decided by the race of their anchor
chains from the deepest common ancestor — at every step the larger available
front goes first, so the chain whose remaining MINIMUM is larger always
exhausts first.  Folding that pairwise rule over all nodes orders them by
lexicographic-descending comparison of each node's suffix-minima chain
(nearest smaller ancestor, then its nearest smaller ancestor, …), and that
comparison is exactly pre-order over T*.  The oracle's convergence across
delivery orders — and the kernel's agreement with it — is pinned by the
random-delivery suites in tests/test_merge_kernel.py.

The whole-tree document order interleaves branches, per the reference's
``walk`` (CRDTree.elm:583-625): a node, then its own branch contents, then
the siblings spliced after it.  So the combined order forest hangs, under
every node, first its child branch's T* roots (group 0), then its
same-branch T* children (group 1), each group timestamp-descending.
Pre-order ranks are computed without recursion by building the Euler tour of
this forest (enter/exit token per node, successor pointers from one sibling
sort) and list-ranking it.

TPU-shaped engineering (the difference between this and a naive lax
translation — v5e has no native int64, sorts are the costliest XLA
primitive at this scale, and random HBM gathers are the bandwidth
bottleneck):

- **No device sort, no device join on the common path.**  The host walks
  every op once at ingest anyway, so it ships dense timestamp RANKS
  (``ts_rank``) and reference POSITIONS (link hints) with the batch
  (codec/packed.py); the kernel scatters ops straight into
  timestamp-ordered int32 slots and resolves every anchor/parent/target
  reference with one verified gather.  In auto mode both hint families
  are re-verified on device — properties that hold iff the hints are
  exactly right — and any violation routes the batch through the
  sort+join construction via ``lax.cond`` (same 10-tuple interface, all
  downstream stages path-agnostic), so wrong hints cost speed, never
  correctness.  Slot ids compare like timestamps everywhere downstream;
  no int64 feeds a sort or a pointer loop.
- **Fused resolution under the chain-length budget (round 6).**  For
  vouched batches the host ALSO ships slot-level hints
  (codec/packed.derive_slot_hints: rank-composed resolutions, the
  anchor's parent slot, the duplicate flag), so the exhaustive trace
  resolves every reference elementwise and the whole node frame rides
  ONE multi-column plane row-gather (pallas bounded-span sweep on TPU,
  ops/fused_resolve.py).  utils/chainaudit.py counts the production
  trace's M-wide memory ops at trace time — ≤16 is CI-pinned
  (tests/test_chain_audit.py) against the measured ~6 ms/op model
  (docs/TPU_PROFILE.md §3-4, §6).
- **Sorts only where contested.**  The one remaining sort — ordering
  sibling groups — runs at a small static width over just the rows whose
  parent has ≥ 2 children (count + prefix-sum compaction); chain-
  dominated logs contract to a few dozen contested rows, and the M-wide
  sort survives only as the adversarial ``lax.cond`` fallback.
- **Exact path validation, one row gather per check.**  "Claimed prefix ==
  parent's materialised path" (what the reference's recursive descent
  checks, Internal/Node.elm:138-163) is one [M, D] gather of the parent's
  materialised path row, compared elementwise under a depth mask against
  the op's own claimed row (already op-indexed — no second gather); the
  delete-target check is the same shape.  Exact equality — no hash, so no
  collision surface for adversarial peers (a fixed-base polynomial hash
  here would let a malicious op forge a colliding path).
- **Fixpoint loops exit early.**  Validity cascading, tombstone-subtree
  propagation and the nearest-smaller-ancestor chase are pointer-doubling
  loops that need their worst-case O(log N) trips only for adversarial
  chains; on causal logs they converge in 0-2 trips.  Each runs as a
  ``lax.while_loop`` with a convergence test and a static trip cap.
- **Run-contracted list ranking.**  The Euler tour of real op logs is
  dominated by ±1-stride index runs (insertion chains produce consecutive
  slots whose tour tokens chain consecutively).  Maximal runs are detected
  elementwise and the whole per-run pipeline — derivation, weighted
  Wyllie doubling, expansion sources — runs at a small static width when
  the run count fits (full width only for fragmented adversarial tours);
  ranks expand back at enter-token width via the pallas monotone-gather
  kernel (ops/mono_gather.py) on TPU.  A 64-chain million-op merge
  contracts to a few hundred list elements.
- **Static all-adds specialization.**  Batches with no deletes (the
  common serving shape) drop the tombstone machinery from the trace via
  a host-checked promise (``host_no_deletes``).

Deletes tombstone a node and kill its whole subtree (a tombstone's children
are discarded, Internal/Node.elm:237-238); tombstones keep their list
position, so they stay in the order forest and are masked only from the
visible sequence.

Sequential-parity statuses: the reference applies a batch in order, so
whether an op is "applied" vs "absorbed" can depend on batch position
(add-under-branch-then-delete logs the add; delete-then-add absorbs it —
the final TREE is identical either way).  The kernel reports a status per op
using batch positions (first-arrival dedup, tombstone-before-me absorption),
exact for causally ordered logs; the converged tree itself is order-
independent.

Reference parity targets: Internal/Node.elm (RGA insert/delete semantics),
CRDTree.elm:275-325 (apply semantics), with the two documented divergences
from crdt_graph_tpu/core/node.py.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..codec.packed import KIND_ADD, KIND_DELETE, MAX_TS
from . import mono_gather, pallas_mode

# Per-op result statuses (sequential parity; see module docstring).
APPLIED = 0
ALREADY_APPLIED = 1   # duplicate add / repeat delete / edit under tombstone
NOT_FOUND = 2         # anchor or delete target missing from its branch
INVALID_PATH = 3      # empty path, missing intermediate, or prefix mismatch
PAD = 4

BIG = MAX_TS          # sorts-after-everything timestamp sentinel (python int:
                      # promotes against int64 arrays without x64-mode issues)
IPOS = 2**31 - 1      # "no position" / +inf for int32 positions


def _env_cap(name: str, default: int) -> int:
    """Static compact-path width, env-overridable (GRAFT_S_CAP /
    GRAFT_R_CAP) so the on-chip tuning session can sweep the caps
    without code edits.  Read at TRACE time: a sweep changing the env
    under identical shapes/static-args must ``jax.clear_caches()`` (or
    use a fresh process) between settings, or the cached trace wins —
    the effective value is logged on every (re)trace so a stale-cache
    sweep is detectable in the log (ADVICE r4)."""
    import logging
    import os
    v = os.environ.get(name)
    cap = int(v) if v else default
    logging.getLogger(__name__).info(
        "trace-time cap %s=%d%s", name, cap,
        "" if v else " (default)")
    return cap


S_CAP_DEFAULT = 1 << 16   # crowded-sibling sort width (merge._finish)
R_CAP_DEFAULT = 1 << 15   # run-pipeline compact width (merge._finish)
# round-7 second compact level: chain-dominated production logs have a
# few dozen contested rows / a few hundred runs, so the static 64k/32k
# widths above overshoot by ~3 orders of magnitude — a nested tiny
# branch (same construction, smaller cap) takes the common case; the
# r6 caps stay as the middle level (XLA-CPU sorts and the unrolled
# binary searches both scale with the static width)
S_CAP2_DEFAULT = 1 << 12
R_CAP2_DEFAULT = 1 << 12


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NodeTable:
    """The converged tree as arrays over M = N + 2 slots.

    Slot 0 is the root; slots 1..N hold nodes (one per unique valid Add —
    unused slots have ``exists=False``); slot M-1 is a null sink.  Document
    order is the RGA walk order; ``order`` lists existing-node slots in that
    order (padded with the null slot), ``visible_order`` the same after
    tombstone/dead masking.
    """

    ts: jax.Array           # i64[M] node timestamp (0 = root, BIG = unused)
    parent: jax.Array       # i32[M] tree-parent slot (root: itself)
    depth: jax.Array        # i32[M]
    value_ref: jax.Array    # i32[M] host value-table index, -1 none
    paths: jax.Array        # i64[M, D] full materialised path, zero-padded
    exists: jax.Array       # bool[M] slot holds a real, valid node
    tombstone: jax.Array    # bool[M] node itself deleted
    dead: jax.Array         # bool[M] some strict ancestor deleted
    visible: jax.Array      # bool[M] exists & ~tombstone & ~dead
    doc_index: jax.Array    # i32[M] position in document order (IPOS if none)
    order: jax.Array        # i32[M] slots of existing nodes in doc order
    visible_order: jax.Array  # i32[M] slots of visible nodes in doc order
    num_nodes: jax.Array    # i32 count of existing nodes
    num_visible: jax.Array  # i32 count of visible nodes
    status: jax.Array       # i8[N] per-op status (original batch order)

    @property
    def capacity(self) -> int:
        return int(self.ts.shape[0]) - 2

    @property
    def null_slot(self) -> int:
        return int(self.ts.shape[0]) - 1


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(max(2, n))))


def _split_ts(t: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int64 timestamp → (hi, lo) int32 sort keys, order-preserving.

    ts < 2^62, so hi = ts >> 32 < 2^30 fits int32 (BIG maps to 2^30); the
    low half is biased into signed range.
    """
    hi = (t >> 32).astype(jnp.int32)
    lo = ((t & 0xFFFFFFFF) - 2**31).astype(jnp.int32)
    return hi, lo


# v5e has no native int64: XLA emulates it, and emulated SCATTERS are the
# one catastrophically slow case (~120-140 ms per M-wide scatter at 1M on
# the live chip vs ~nothing for int32; gathers and elementwise i64 are
# fine — scripts/probe_stage12.py).  Every scatter of an i64 value array
# therefore runs as TWO int32 scatters of the bit halves below, repacked
# elementwise afterwards.

BIG_HI = BIG >> 32                       # unbiased bit halves of BIG
BIG_LO_BIASED = (BIG & 0xFFFFFFFF) - 2**31


def _split_u(t: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int64 → (hi, lo) raw int32 bit halves (no bias — equality and
    repack exact for 0 <= t < 2^63; NOT order-preserving)."""
    return (t >> 32).astype(jnp.int32), (t & 0xFFFFFFFF).astype(jnp.int32)


def _pack_u(h: jax.Array, l: jax.Array) -> jax.Array:
    """Inverse of :func:`_split_u` (elementwise, cheap on TPU)."""
    return (h.astype(jnp.int64) << 32) | (l.astype(jnp.int64) & 0xFFFFFFFF)


def _pack_biased(h: jax.Array, l: jax.Array) -> jax.Array:
    """Inverse of :func:`_split_ts` (biased low halves, sort keys)."""
    return (h.astype(jnp.int64) << 32) | (l.astype(jnp.int64) + 2**31)


def _fix_and(ok: jax.Array, ptr: jax.Array, cap: int) -> jax.Array:
    """AND of ``ok`` over every ancestor along ``ptr`` chains (terminal
    slots self-loop).  Pointer doubling with early exit: 0 trips when all
    ok, log(chain depth) when something is invalid.  The static ``cap``
    guarantees termination even on adversarial pointer cycles, which
    doubling never collapses to self-loops."""
    def cond(state):
        ok, _, live, i = state
        return live & (i < cap) & jnp.any(~ok)

    def body(state):
        ok, ptr, _, i = state
        ok2 = ok & ok[ptr]
        ptr2 = ptr[ptr]
        return ok2, ptr2, jnp.any(ptr2 != ptr), i + 1

    ok, _, _, _ = lax.while_loop(
        cond, body, (ok, ptr, jnp.array(True), jnp.int32(0)))
    return ok


def _fix_min(val: jax.Array, ptr: jax.Array, active: jax.Array,
             cap: int) -> jax.Array:
    """MIN of ``val`` over self + every ancestor along ``ptr`` chains.
    Skipped entirely when ``active`` is false (no deletes in the batch)."""
    def cond(state):
        _, _, live, i = state
        return live & (i < cap)

    def body(state):
        val, ptr, _, i = state
        val2 = jnp.minimum(val, val[ptr])
        ptr2 = ptr[ptr]
        return val2, ptr2, jnp.any(ptr2 != ptr), i + 1

    val, _, _, _ = lax.while_loop(
        cond, body, (val, ptr, active, jnp.int32(0)))
    return val


def _sorted_slots_impl(is_add, ts, pos, N, M, ROOT, NULL):
    """Sort-based slot assignment (see the SORTED+JOIN contract in
    ``_materialize``): the first six tuple entries plus the sorted
    timestamp axis the join needs."""
    sort_ts = jnp.where(is_add & (ts > 0), ts, BIG)
    ts_hi, ts_lo = _split_ts(sort_ts)
    # stable sort: equal timestamps keep batch order; per-node fields
    # re-derive by gathers through node_row — cheaper than more arrays
    # through the sort network
    s_hi, s_lo, sorted_idx = lax.sort(
        (ts_hi, ts_lo, jnp.arange(N, dtype=jnp.int32)), num_keys=2)
    sorted_ts = (s_hi.astype(jnp.int64) << 32) | \
        (s_lo.astype(jnp.int64) + 2**31)
    run_start = jnp.concatenate(
        [jnp.ones(1, bool),
         (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
    not_big = s_hi < (BIG >> 32)
    is_canon = run_start & not_big
    # slot of the run's canonical add = run-start index + 1
    canon_pos = lax.cummax(jnp.where(run_start,
                                     jnp.arange(N, dtype=jnp.int32), 0))
    slot_of_sorted = canon_pos + 1
    # per-op slot + duplicate flag (original batch order).  sorted_idx
    # is a permutation — unique indices keep XLA's TPU scatter on the
    # parallel path instead of the serialized duplicate-safe one.
    op_slot = jnp.full(N, NULL, jnp.int32).at[sorted_idx].set(
        jnp.where(not_big, slot_of_sorted, NULL), unique_indices=True)
    op_is_dup = jnp.zeros(N, bool).at[sorted_idx].set(
        ~run_start & not_big, unique_indices=True)
    # canonical SOURCE ROW per slot (original batch order), the one
    # node-frame scatter this construction keeps: every other node
    # column derives by gathering the canonical row's op fields through
    # it — M-wide scatters have a large fixed per-element cost on v5e
    # while random gathers are far cheaper (scripts/probe_prims.py)
    tgt = jnp.where(is_canon, slot_of_sorted, M)
    node_row = jnp.full(M, IPOS, jnp.int32).at[tgt].set(
        sorted_idx, mode="drop", unique_indices=True)
    is_node_slot, node_ts, node_pos = _node_cols_from_row(
        node_row, sort_ts, pos, M, ROOT, N)
    return (op_slot, op_is_dup, node_ts, node_pos,
            is_node_slot, node_row), sorted_ts


def _join_ops_impl(sorted_ts, parent_ts, at_ts, N, ROOT, NULL):
    """Per-op sort-merge join (2N queries: parent and anchor-or-target
    against the sorted add axis; method="sort": the per-query binary
    search was 1.67 s device time at 1M ops on v5e).  ``at_ts`` is the
    FUSED anchor/target column — anchor ts for Add rows, own (target) ts
    for Delete rows: downstream consumes the anchor resolution only at
    canonical Add rows and the target resolution only at Delete rows
    (_finish: af_pack scatter / d_tslot), so one resolution serves both
    and the join shrinks from 3N to 2N queries.  Hint-verified merges
    defer it into a cond branch that never executes."""
    queries = jnp.concatenate([parent_ts, at_ts])
    qidx = jnp.searchsorted(sorted_ts, queries, side="left",
                            method="sort").astype(jnp.int32)
    qidx_c = jnp.minimum(qidx, N - 1)
    qhit = (sorted_ts[qidx_c] == queries) & (queries > 0) & \
        (queries < BIG)
    qslot = jnp.where(queries == 0, ROOT,
                      jnp.where(qhit, qidx_c + 1, NULL)) \
        .astype(jnp.int32)
    qfound = (queries == 0) | qhit
    return (qslot[:N], qslot[N:],
            qfound[:N], qfound[N:])


def _at_ts(is_add, anchor_ts, ts):
    """The fused anchor-or-target timestamp column (see
    :func:`_join_ops_impl`)."""
    return jnp.where(is_add, anchor_ts, ts)


def _node_cols_from_row(node_row, src_ts, src_pos, M, ROOT, N):
    """Node-frame columns by GATHER through the canonical source row.

    ``node_row`` (i32[M], ≥ N ⇒ unused slot) is the one scattered frame
    each construction keeps; the ts/pos columns derive from it with one
    gather each instead of one scatter each (M-wide scatters have a
    large fixed per-element cost on v5e while random gathers are far
    cheaper — scripts/probe_prims.py).  Shared by the ranked path and
    the sorted fallback so the two constructions cannot drift.  Unused
    slots: ts = BIG (sorts last), pos = IPOS; ROOT's ts overridden
    to 0."""
    is_node_slot = node_row < jnp.int32(N)
    wc = jnp.where(is_node_slot, node_row, 0)
    # one [N, 2] i64 row gather instead of two column gathers
    src = jnp.stack([src_ts, src_pos.astype(jnp.int64)], axis=-1)
    g = src[wc]
    got_ts, got_pos = g[:, 0], g[:, 1].astype(jnp.int32)
    node_ts = jnp.where(is_node_slot, got_ts, BIG)
    node_ts = jnp.where(jnp.arange(M, dtype=jnp.int32) == ROOT,
                        jnp.int64(0), node_ts)
    node_pos = jnp.where(is_node_slot, got_pos, IPOS)
    return is_node_slot, node_ts, node_pos


def _plane_rows(plane: jax.Array, idx: jax.Array,
                use_pallas) -> jax.Array:
    """The node-frame plane row-gather (``plane[idx]``).  On TPU the
    pallas bounded-span sweep (ops/fused_resolve.py) with its in-trace
    lax fallback; the lax gather elsewhere — bit-identical either way
    (tests/test_fused_resolve.py)."""
    from . import fused_resolve
    return fused_resolve.plane_rows(plane, idx, use_pallas=use_pallas)


def _plane_rows2(plane: jax.Array, idx: jax.Array, hop_col: int,
                 use_pallas) -> Tuple[jax.Array, jax.Array]:
    """The 2-hop node-frame sweep: ``g = plane[idx]`` and
    ``g2 = plane[clip(g[:, hop_col], 0, R-1)]`` — the round-7
    resolution superop (ops/fused_resolve.plane_rows2): one pallas
    VMEM-tiled pass on TPU for both dependent gathers, the two lax
    gathers elsewhere, bit-identical either way."""
    from . import fused_resolve
    return fused_resolve.plane_rows2(plane, idx, hop_col,
                                     use_pallas=use_pallas)


def _pack_slot_or_neg(is_add, op_slot_arr):
    """``is_add`` and ``op_slot`` fused into one gatherable column:
    the op's slot for Add rows, -1 otherwise (op_slot is never negative,
    so ``>= 0`` recovers is_add exactly).  Computed ONCE by the caller
    and shared by all three hint resolutions — halves their per-hint
    gather count on v5e, where each M-wide random gather has a fixed
    per-op cost."""
    return jnp.where(is_add, op_slot_arr, -1).astype(jnp.int32)


def _res_hint_impl(hint, want, slot_or_neg, ts, N, ROOT, NULL,
                   check_ts: bool = True):
    """One link-hint resolution: verified int32 gather (see the
    RANKED+HINTED contract in ``_materialize``).  ``miss`` flags any
    nonzero reference without a verified hint.  ``slot_or_neg`` (from
    :func:`_pack_slot_or_neg`) and ``ts`` are the summary columns the
    hint indexes into (the whole batch).

    ``check_ts=True`` (auto mode) verifies each hint on device with a
    second gather (``ts[hint] == want``) — required for the "wrong
    hints cost speed, never correctness" guarantee.  ``check_ts=False``
    (exhaustive mode) trusts the VOUCHED producer contract — every
    producer (codec/packed.pack, rebuild_hints, concat, the native
    parser) emits ``-1`` for any reference with no matching in-batch
    add row, and ``packed.verify_hints`` re-audits exactly that (incl.
    no stray out-of-batch hints) on every restore/foreign ingest — so
    resolution is ONE i32 gather per hint; an M-wide i64 check gather
    was ~1/6 of the kernel's device time at 1M on v5e."""
    p = jnp.clip(hint, 0, N - 1)
    sp = slot_or_neg[p]
    ok = (hint >= 0) & (sp >= 0) & (want > 0) & (want < BIG)
    if check_ts:
        ok = ok & (ts[p] == want)
    slot = jnp.where(want == 0, ROOT, jnp.where(ok, sp, NULL))
    miss = (want > 0) & (want < BIG) & ~ok
    return slot.astype(jnp.int32), (want == 0) | ok, miss


def crowding_hinted(ops, hints, no_deletes: bool) -> bool:
    """Trace-time predicate for the sibling-crowding static skip: the
    host derived (and VERIFIED — codec/packed.derive_crowding_hints)
    the crowding structure, so the scatter-add + gather + cumsum trio
    drops out of the trace.  Mirrors ``_finish``'s gate exactly (the
    fused slot-hint resolution + the crowd columns) so utils/chainaudit
    can record which leg a batch's trace runs."""
    have_link = all(k in ops for k in ("parent_pos", "anchor_pos",
                                       "target_pos"))
    have_slot = hints == "exhaustive" and have_link and \
        "ts_rank" in ops and all(
            k in ops for k in ("parent_sl", "at_sl", "anchor_psl",
                               "dup_row"))
    # the trio only exists on the compacted sibling branch (S_CAP < M,
    # _finish); below that width both legs compile the same trace and
    # no leg is "hinted"
    n = ops["kind"].shape[0] if "kind" in ops else 0
    compacted = _env_cap("GRAFT_S_CAP", S_CAP_DEFAULT) < n + 2
    return (have_slot and no_deletes and compacted and
            "crowd_slot" in ops and "crowd_cpos" in ops)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _materialize(ops: Dict[str, jax.Array],
                 use_pallas: Optional[bool] = None,
                 hints: Optional[str] = None,
                 no_deletes: bool = False,
                 part=None) -> NodeTable:
    """``use_pallas``: pallas usage for every kernel of the trace
    (ops/pallas_mode.select: None = auto — Mosaic kernel on TPU
    backends, lax elsewhere; ``"interpret"`` = the interpreter);
    wrappers whose transforms the pallas call must not see (vmapped
    batched merges, the ops-axis shard_map body) pass False —
    a distinct static-arg jit entry, so traces never leak across.

    ``hints``: link-hint policy for timestamp resolution (step 4).
    None/"auto" = use hints with a runtime lax.cond fallback to the
    sort-join when any reference lacks a verified hint; "exhaustive" =
    trust the producer's hint coverage (pack/concat guarantee it) and
    compile the hinted path ONLY — no cond, so the trace is vmappable
    and partitionable and the join never compiles; "join" = ignore
    hints entirely.  Results are identical across modes for batches
    with exhaustive hints (pinned by tests).

    ``no_deletes``: static promise that no row is a Delete (the caller
    checked the kind column host-side — ``materialize`` does this
    automatically for numpy inputs).  Skips the tombstone/dead-subtree
    machinery (steps 7-8) and the delete statuses at trace time — the
    common all-adds serving batch compiles and runs leaner.  A violated
    promise would silently ignore deletes, so only host-checked callers
    set it.

    ``part``: optional ops-axis partition context
    (parallel/opsaxis.OpsAxisPart).  When set, the trace is being built
    INSIDE a shard_map body and the billed M-wide memory ops route
    through the context's sharded implementations (halo-windowed plane
    gathers, all-reduce-joined frame scatters, ring-carry chunked
    scans) — ceil(M/k) width per device, bit-identical results.  Only
    reachable via ``_materialize.__wrapped__`` (a Python object cannot
    cross the jit signature)."""
    kind = ops["kind"]
    ts = ops["ts"].astype(jnp.int64)
    parent_ts = ops["parent_ts"].astype(jnp.int64)
    anchor_ts = ops["anchor_ts"].astype(jnp.int64)
    depth = ops["depth"].astype(jnp.int32)
    paths = ops["paths"].astype(jnp.int64)
    value_ref = ops["value_ref"].astype(jnp.int32)
    pos = ops["pos"].astype(jnp.int32)

    N = kind.shape[0]
    D = paths.shape[1]
    M = N + 2
    ROOT = 0
    NULL = M - 1
    slot_ids = jnp.arange(M, dtype=jnp.int32)

    if hints not in (None, "auto", "exhaustive", "join"):
        raise ValueError(f"unknown hints mode {hints!r}; expected None, "
                         "'auto', 'exhaustive', or 'join'")

    is_add = kind == KIND_ADD
    is_del = kind == KIND_DELETE

    # ---- 2. Column index row, shared by the masked path compares below.
    cols = jnp.arange(D, dtype=jnp.int32)[None, :]

    # ---- 1-4. Slot assignment and timestamp→slot resolution.  Two
    # interchangeable constructions of one interface (the 10-tuple
    # described below); all downstream stages are path-agnostic.
    #
    # SORTED+JOIN (always available): one stable (hi, lo) int32 key sort
    # of the add timestamps assigns dense slots (slot order IS timestamp
    # order; first array row wins duplicates — producers keep ``pos ==
    # array index``, codec/packed.py), then a per-op sort-merge join
    # resolves the 3N timestamp references (method="sort": the per-query
    # binary search was 1.67 s device time at 1M ops on v5e).
    #
    # RANKED+HINTED (ingest hints): ``ts_rank`` assigns slots directly
    # (slot = rank+1, canonical copy = min array row per slot, one
    # scatter-min) and link-hint columns resolve each reference with one
    # verified int32 gather — no sort, no join: the full-width device
    # sort was the kernel's single most expensive stage on v5e.  In auto
    # mode the ranks are VERIFIED on device (dense used-slot prefix,
    # strictly increasing slot timestamps, every add ranked, duplicates
    # agreeing — these four properties hold iff the ranks are exactly the
    # unique-timestamp ranks) and the link hints are verified per
    # reference (``ts[hint] == referenced_ts``); ANY violation sends the
    # whole batch down the sorted+join branch via lax.cond, so wrong
    # hints cost speed, never correctness.  In "exhaustive" mode the
    # caller VOUCHES for hint coverage (pack/concat provenance) and the
    # sort/join never compile — a violated promise there silently
    # mis-resolves, which is why the mode is opt-in per call site.
    # Branch interface — everything per-op (N-wide) except the three node
    # arrays the rank verification shares (node_ts, node_pos,
    # is_node_slot); the rest of the node table is constructed ONCE after
    # selection, so the auto-mode lax.cond never carries the [M, D] path
    # plane or the resolution scatters as operands:
    #   (op_slot, op_is_dup, node_ts, node_pos, is_node_slot, node_row,
    #    pp_slot, at_slot, pp_found, at_found)
    # ``node_row`` is each used slot's canonical SOURCE ROW (IPOS when
    # unused): _finish gathers the remaining per-node fields (depth,
    # value_ref, path plane, resolved links) through it instead of
    # scattering them — the node-frame construction keeps exactly one
    # M-wide scatter per path (win / the sorted construction's row
    # scatter).
    # ``at`` is the FUSED anchor-or-target resolution (anchor for Add
    # rows, delete target for Delete rows — see _join_ops_impl): the two
    # are consumed at disjoint row sets downstream, so resolving them
    # separately paid one extra M-wide random gather pair per merge.
    # The delete-parent resolution is the per-op parent resolution
    # (dp ≡ pp), so it needs no slots of its own.
    at_ts = _at_ts(is_add, anchor_ts, ts)

    def _sorted_slots():
        return _sorted_slots_impl(is_add, ts, pos, N, M, ROOT, NULL)

    def _join_ops(sorted_ts):
        return _join_ops_impl(sorted_ts, parent_ts, at_ts,
                              N, ROOT, NULL)

    def _sorted_ops(_):
        slots, sorted_ts = _sorted_slots()
        return slots + _join_ops(sorted_ts)

    def _resolve_hinted(op_slot_arr):
        son = _pack_slot_or_neg(is_add, op_slot_arr)
        # exhaustive mode rides the vouched producer contract and skips
        # the per-hint ts check gather (_res_hint_impl docstring)
        check = hints != "exhaustive"

        def _res_hint(hint, want):
            return _res_hint_impl(hint, want, son, ts, N, ROOT, NULL,
                                  check_ts=check)

        pp = _res_hint(ops["parent_pos"].astype(jnp.int32), parent_ts)
        at = _res_hint(
            jnp.where(is_add, ops["anchor_pos"].astype(jnp.int32),
                      ops["target_pos"].astype(jnp.int32)), at_ts)
        return pp, at

    have_link = hints != "join" and all(
        k in ops for k in ("parent_pos", "anchor_pos", "target_pos"))
    have_rank = have_link and "ts_rank" in ops
    # SLOT hints (codec.packed.derive_slot_hints): the host composed the
    # position hints with the ranks, so the vouched exhaustive mode
    # resolves every reference ELEMENTWISE — no resolution gathers at
    # all; the node-frame columns ride _finish's fused plane gather.
    # Only meaningful under the vouched contract: the auto mode keeps
    # the gather-based per-reference verification.
    have_slot = hints == "exhaustive" and have_rank and all(
        k in ops for k in ("parent_sl", "at_sl", "anchor_psl", "dup_row"))

    def _win_frame(has_rank_arr, op_slot_arr):
        """The winner scatter-min (min array row per slot), part-routed
        when partitioned (per-device index width N/k + psum-style
        pmin join, parallel/opsaxis.py)."""
        row_idx = jnp.arange(N, dtype=jnp.int32)
        tgt = jnp.where(has_rank_arr, op_slot_arr, M)
        if part is not None:
            return part.frame_reduce(M, IPOS, tgt, row_idx, "min")
        return jnp.full(M, IPOS, jnp.int32).at[tgt].min(row_idx,
                                                        mode="drop")

    if have_slot:
        rank = ops["ts_rank"].astype(jnp.int32)
        is_real_add = is_add & (ts > 0) & (ts < BIG)
        has_rank = is_real_add & (rank >= 0) & (rank < N)
        op_slot_r = jnp.where(has_rank, rank + 1, NULL).astype(jnp.int32)
        # duplicate election: host-precomputed first-array-row-wins flag
        if "win_row" in ops:
            # winner frame host-elected too (codec.packed win_row): the
            # whole resolution stage is elementwise — zero M-wide
            # memory ops (round 7; the scatter-min was the last one)
            pad = jnp.full(1, IPOS, jnp.int32)
            win = jnp.concatenate(
                [pad, ops["win_row"].astype(jnp.int32), pad])
        else:
            win = _win_frame(has_rank, op_slot_r)
        op_is_dup_r = ops["dup_row"].astype(bool) & has_rank
        is_node_slot_r = win < jnp.int32(N)
        pf = ops["parent_sl"].astype(jnp.int32)
        af = ops["at_sl"].astype(jnp.int32)
        # node_ts/node_pos = None: _finish derives them from its fused
        # node-frame plane gather (one M-wide sweep instead of a
        # separate stage-1 gather pair)
        sel = (op_slot_r, op_is_dup_r, None, None,
               is_node_slot_r, win,
               pf >> 1, af >> 1,
               (pf & 1).astype(bool), (af & 1).astype(bool))
    elif have_rank:
        rank = ops["ts_rank"].astype(jnp.int32)
        is_real_add = is_add & (ts > 0) & (ts < BIG)
        has_rank = is_real_add & (rank >= 0) & (rank < N)
        op_slot_r = jnp.where(has_rank, rank + 1, NULL).astype(jnp.int32)
        # canonical copy = min ARRAY ROW per slot — the same winner rule
        # as the sorted construction's stable sort (first array row wins),
        # independent of the pos column, so a producer violating the
        # pos == array-index contract cannot make the two paths disagree
        row_idx = jnp.arange(N, dtype=jnp.int32)
        win = _win_frame(has_rank, op_slot_r)
        win_back = part.gather_rows(win, op_slot_r) if part is not None \
            else win[op_slot_r]
        is_canon_op = has_rank & (row_idx == win_back)
        op_is_dup_r = has_rank & ~is_canon_op
        # Node columns by GATHER through the winner row — the scatter-min
        # above is the ONE scatter this construction keeps (the former
        # four M-wide scatters were most of stage 1's 270 ms of the
        # 396 ms clean kernel on the live chip); win already encodes
        # exactly which row owns each slot: unused slots (and ROOT/NULL,
        # which no op targets — slot = rank+1 ∈ [1, N]) keep IPOS.
        is_node_slot_r, node_ts_r, node_pos_r = _node_cols_from_row(
            win, ts, pos, M, ROOT, N)

        ((pp_slot, pp_found, pp_miss),
         (at_slot, at_found, at_miss)) = _resolve_hinted(op_slot_r)
        ranked = (op_slot_r, op_is_dup_r, node_ts_r, node_pos_r,
                  is_node_slot_r, win,
                  pp_slot, at_slot, pp_found, at_found)
        if hints == "exhaustive":
            sel = ranked
        else:
            # rank verification: the four properties below hold iff
            # ts_rank is exactly the unique-add-timestamp rank
            used = is_node_slot_r
            nts = node_ts_r
            dense_ok = jnp.all(~used[2:M - 1] | used[1:M - 2])
            incr_ok = jnp.all(jnp.where(used[1:M - 1] & used[2:M],
                                        nts[1:M - 1] < nts[2:M], True))
            ts_match = jnp.all(
                jnp.where(has_rank, nts[jnp.clip(op_slot_r, 0, M - 1)]
                          == ts, True))
            all_ranked = jnp.all(~is_real_add | has_rank)
            link_miss = jnp.any(pp_miss) | \
                jnp.any(at_miss & (is_add | is_del))
            hints_ok = dense_ok & incr_ok & ts_match & all_ranked & \
                ~link_miss
            sel = lax.cond(hints_ok, lambda _: ranked, _sorted_ops, None)
    elif have_link:
        # link hints without ranks: sorted slot assignment runs eagerly,
        # hinted resolution with per-reference verification; the JOIN
        # stays inside the cond fallback so verified-hint merges never
        # execute it
        slots, sorted_ts = _sorted_slots()
        ((pp_slot, pp_found, pp_miss),
         (at_slot, at_found, at_miss)) = _resolve_hinted(slots[0])
        hinted = (pp_slot, at_slot, pp_found, at_found)
        if hints == "exhaustive":
            resolution = hinted
        else:
            any_miss = jnp.any(pp_miss) | \
                jnp.any(at_miss & (is_add | is_del))
            resolution = lax.cond(
                any_miss, lambda _: _join_ops(sorted_ts),
                lambda _: hinted, None)
        sel = slots + tuple(resolution)
    else:
        sel = _sorted_ops(None)

    return _finish(ops, sel, use_pallas, no_deletes, part=part)


def _finish(ops: Dict[str, jax.Array], sel, use_pallas: Optional[bool],
            no_deletes: bool, part=None) -> NodeTable:
    """Stages 3-13: node-table construction through per-op statuses,
    from the resolution interface (the 10-tuple ``sel``).

    FUSED variant: a resolution built from host-derived slot hints
    passes ``node_ts = node_pos = None`` (and ships ``anchor_psl`` in
    ``ops``); both columns — plus the anchor-parent slot the sibling
    check needs — are then derived from the one node-frame plane
    row-gather below, so the entire frame construction is a single
    M-wide sweep (the chain-length budget, utils/chainaudit.py)."""
    kind = ops["kind"]
    ts = ops["ts"].astype(jnp.int64)
    anchor_ts = ops["anchor_ts"].astype(jnp.int64)
    depth = ops["depth"].astype(jnp.int32)
    paths = ops["paths"].astype(jnp.int64)
    value_ref = ops["value_ref"].astype(jnp.int32)
    pos = ops["pos"].astype(jnp.int32)
    N = kind.shape[0]
    D = paths.shape[1]
    M = N + 2
    ROOT = 0
    NULL = M - 1
    slot_ids = jnp.arange(M, dtype=jnp.int32)
    cols = jnp.arange(D, dtype=jnp.int32)[None, :]
    is_add = kind == KIND_ADD
    is_del = kind == KIND_DELETE
    (op_slot, op_is_dup, node_ts, node_pos, is_node_slot, node_row,
     pp_slot, at_slot, pp_found, at_found) = sel
    # FUSED node frame (slot-hint resolution, merge._materialize): the
    # resolution stage shipped no node_ts/node_pos — they are derived
    # below from the same plane gather as every other node column, so
    # the whole node-frame construction is ONE M-wide sweep.
    fused = node_ts is None
    # under the no-deletes promise visible ≡ exists: the rank pipeline
    # carries one weight lane (step 12)
    single_w = no_deletes

    # ---- 3. Node-table construction from the SELECTED assignment —
    # shared across all branches, outside any cond, and SCATTER-FREE:
    # every per-node field is the canonical source row's op field,
    # gathered through ``node_row`` (M-wide scatters have a large fixed
    # per-element cost on v5e — stage 2 measured 62 ms of the 396 ms
    # clean kernel as scatters, scripts/probe_prims.py — while the
    # whole construction is 3 gathers sharing one index vector).
    nsr = jnp.where(is_node_slot, node_row, 0)
    # small per-op fields pre-fused into ONE gatherable i64: hi word =
    # depth(5b)+anchor-sentinel(1b), lo word = value_ref
    dsv_src = _pack_u((depth << 1) | (anchor_ts == 0), value_ref)
    # both resolved links (slot(30b)+found(1b) each) in ONE i64 gather;
    # at_slot/at_found carry the anchor resolution at Add rows and the
    # delete-target resolution at Delete rows (fused upstream): canon
    # rows are Adds, so the gathered half sees anchors; d_tslot is read
    # at Delete rows only (step 7), where the fused column IS the target.
    pa = _pack_u((pp_slot << 1) | pp_found, (at_slot << 1) | at_found)
    extra = []
    # 2nd-hop parent frame (round 7): with the host-shipped parent_row
    # column riding the plane, the parent's materialised path/depth
    # re-derive elementwise from its SOURCE ROW (second gather of the
    # same plane), and both hops fuse into one pallas superop
    # (plane_rows2).  DEVICE-ONLY: the trick trades a narrow [M, D+1]
    # gather for a second full-plane hop — one fused VMEM pass on TPU
    # (op COUNT is what the chain budget prices there), but ~2x the
    # random bytes on the lax/CPU path, where bytes are what cost
    # (measured: stage 2 of the CPU fallback bench regressed 62 →
    # 202 ms under the 2-hop lax fallback); the lax trace keeps the
    # round-6 fp-plane gather through pslot.
    dev_pallas, _ = pallas_mode.select(use_pallas)
    fused2 = fused and "parent_row" in ops and dev_pallas
    if fused:
        # hi = the anchor row's own parent resolution (what the sibling
        # check read as pslot[aslot]); lo = batch position; plus the raw
        # timestamp column — node_ts/node_pos/anchor-parent all ride the
        # one plane row-gather instead of their own M-wide passes
        ap_src = _pack_u(ops["anchor_psl"].astype(jnp.int32), pos)
        extra = [ap_src[:, None], ts[:, None]]
        if fused2:
            extra = extra + [ops["parent_row"].astype(jnp.int64)[:, None]]
    # parent_row's plane column: always the LAST extra (derived, not
    # hardcoded — a wrong hop column would rebuild parent frames from
    # whatever column sits there, silently corrupting validity)
    HOP_COL = 2 + len(extra) - 1
    # all nsr-indexed gathers ride one [N, D+2(+2|+3)] i64 plane row
    plane = jnp.concatenate(
        [dsv_src[:, None], pa[:, None]] + extra + [paths], axis=1)
    if fused2:
        g, g2 = _plane_rows2(plane, nsr, HOP_COL, use_pallas)
    elif part is not None:
        # ops-axis sharded: each device sweeps only its own slot
        # range's rows through a halo window (span violation falls
        # back to this very lax gather — parallel/opsaxis.py)
        g = part.plane_rows(plane, nsr)
    else:
        g = _plane_rows(plane, nsr, use_pallas)
    k = 2 + len(extra)
    dsv, pa_g, claimed_raw = g[:, 0], g[:, 1], g[:, k:]
    if fused:
        ap_g, ts_g = g[:, 2], g[:, 3]
        node_ts = jnp.where(is_node_slot, ts_g, BIG)
        node_ts = jnp.where(slot_ids == ROOT, jnp.int64(0), node_ts)
        node_pos = jnp.where(is_node_slot,
                             (ap_g & 0xFFFFFFFF).astype(jnp.int32), IPOS)
        # anchor-parent slot+found, masked like pa_n below (non-node
        # slots read as NULL, matching what pslot[aslot] would yield)
        ansl = jnp.where(is_node_slot, (ap_g >> 32).astype(jnp.int32),
                         jnp.int32(NULL << 1))
    node_depth = jnp.where(is_node_slot, (dsv >> 33).astype(jnp.int32),
                           0).at[ROOT].set(0)
    node_anchor_is_sentinel = is_node_slot & \
        ((dsv >> 32) & 1).astype(bool)
    node_value_ref = jnp.where(is_node_slot,
                               (dsv & 0xFFFFFFFF).astype(jnp.int32), -1)
    # the path planes stay SPLIT as raw int32 bit halves through every
    # compare below (prefix + delete-target checks are pure equality) and
    # repack to the i64 output plane once at the end; one [M, D] i64 row
    # gather replaces what was the kernel's costliest single scatter pair
    claimed = jnp.where(is_node_slot[:, None], claimed_raw, 0)
    claimed_h, claimed_l = _split_u(claimed)
    pa_n = jnp.where(is_node_slot, pa_g,
                     _pack_u(jnp.full(M, NULL << 1, jnp.int32),
                             jnp.full(M, NULL << 1, jnp.int32)))
    pf_pack = (pa_n >> 32).astype(jnp.int32)
    af_pack = (pa_n & 0xFFFFFFFF).astype(jnp.int32)
    pslot, pfound = pf_pack >> 1, (pf_pack & 1).astype(bool)
    aslot, afound = af_pack >> 1, (af_pack & 1).astype(bool)
    d_tslot, d_tfound = at_slot, at_found
    dp_slot, dp_found = pp_slot, pp_found
    pslot = jnp.where(slot_ids == ROOT, ROOT, pslot)

    # Full materialised path: claimed anchor path with the node's own ts
    # in the last position (Internal/Node.elm:79-82).  The row index of
    # this update is the identity, so it lowers as a one-hot elementwise
    # select over the plane, never a scatter.
    col = jnp.clip(node_depth - 1, 0, D - 1)
    nts_h, nts_l = _split_u(node_ts)
    put = (cols == col[:, None]) & (node_depth[:, None] > 0)
    fp_h = jnp.where(put, nts_h[:, None], claimed_h)
    fp_l = jnp.where(put, nts_l[:, None], claimed_l)

    # ---- 5. Local validity per node slot: the claimed prefix must exactly
    # match the parent's materialised path (what "descending the path"
    # validates in the reference, Internal/Node.elm:138-163), the anchor
    # must be a sibling (same parent), depths must chain.
    if fused2:
        # parent frame from the plane's second hop: the parent slot's
        # materialised path is its claimed path with its own timestamp
        # placed at depth-1 — exactly how fp is built per slot below —
        # re-derived here from the parent's SOURCE ROW (g2).  Slots
        # whose parent is the root, unresolved, or absent read a zeroed
        # frame, matching what fp[ROOT]/fp[NULL]/unused rows held (the
        # prefix/depth checks are gated by pfound either way).
        pvalid = is_node_slot & (g[:, HOP_COL] >= 0)
        par_depth = jnp.where(pvalid,
                              (g2[:, 0] >> 33).astype(jnp.int32), 0)
        pc = jnp.where(pvalid[:, None], g2[:, k:], 0)
        pc_h, pc_l = _split_u(pc)
        pts_h, pts_l = _split_u(jnp.where(pvalid, g2[:, 3],
                                          jnp.int64(0)))
        put_p = (cols == jnp.clip(par_depth - 1, 0, D - 1)[:, None]) & \
            (par_depth[:, None] > 0)
        par_h = jnp.where(put_p, pts_h[:, None], pc_h)
        par_l = jnp.where(put_p, pts_l[:, None], pc_l)
    else:
        # parent path plane + parent depth in one [M, D+1] i64 row
        # gather through pslot; the fp repack of the kernel's output
        # plane (the NodeTable ``paths``) is the same _pack_u
        # expression, so XLA CSEs it — the pack itself costs nothing
        # extra
        pplane_src = jnp.concatenate(
            [_pack_u(fp_h, fp_l), node_depth[:, None].astype(jnp.int64)],
            axis=1)
        pplane = part.plane_rows(pplane_src, pslot) \
            if part is not None else pplane_src[pslot]
        par_h, par_l = _split_u(pplane[:, :D])
        par_depth = pplane[:, D].astype(jnp.int32)
    prefix_ok = jnp.all(
        jnp.where(cols < node_depth[:, None] - 1,
                  (claimed_h == par_h) & (claimed_l == par_l),
                  True), axis=1)
    depth_ok = (node_depth >= 1) & (node_depth <= D) & \
        (node_depth == par_depth + 1)
    parent_ok = pfound & depth_ok & prefix_ok
    if fused:
        # the anchor's parent slot was host-derived and rode the plane
        # gather (``ansl``): the sibling check is elementwise instead of
        # one more M-wide gather through aslot
        anchor_parent = ansl >> 1
    elif part is not None:
        anchor_parent = part.gather_rows(pslot, aslot)
    else:
        anchor_parent = pslot[aslot]
    anchor_ok = node_anchor_is_sentinel | \
        (afound & (anchor_parent == pslot) & (aslot != ROOT))
    local_ok = is_node_slot & (node_ts > 0) & parent_ok & anchor_ok
    local_ok = local_ok.at[ROOT].set(True)

    # ---- 6. Validity cascades along the anchor forest: a node exists only
    # if its anchor chain and tree ancestors all exist.  Parked slots are
    # masked "ok" during the cascade so the all-ops-valid fast path exits in
    # zero trips (no valid node's chain depends on a parked slot: pointing
    # at one implies pfound/afound already failed), then masked back out.
    order_parent = jnp.where(node_anchor_is_sentinel, pslot, aslot)
    order_parent = order_parent.at[ROOT].set(ROOT).at[NULL].set(NULL)
    cascade_ok = _fix_and(local_ok | ~is_node_slot, order_parent,
                          _ceil_log2(M) + 1)

    # ---- 6b. Anchor-CYCLE rejection.  An adversarial op set can close a
    # loop of same-branch anchors (a anchored at b, b at a): every member
    # is locally ok and the AND-cascade over the cycle stays true, yet no
    # serial application order admits any member — the reference rejects
    # them all (each one's anchor is absent when it arrives).  A cycle
    # must contain an edge whose anchor has a LARGER slot, so causal logs
    # (and the sentinel-anchored combs) skip this entirely; when such an
    # edge exists, full pointer-squaring reachability flags every node
    # whose chain never reaches a terminal (ROOT/NULL).  Parent edges
    # cannot cycle (depth strictly decreases), so order_parent covers
    # the whole graph.
    # >= : a SELF-anchored op (anchor ts == own ts) is a 1-cycle and must
    # route through the reachability check too (its self-loop is not a
    # terminal, so it gets flagged like longer loops)
    up_edge = jnp.any(is_node_slot & ~node_anchor_is_sentinel &
                      (aslot != NULL) & (aslot >= slot_ids))

    def _reaches_terminal(ptr):
        k_cap = _ceil_log2(M) + 1

        def body(state):
            p, i = state
            return p[p], i + 1

        p, _ = lax.while_loop(lambda s: s[1] < k_cap, body,
                              (ptr, jnp.int32(0)))
        return (p == ROOT) | (p == NULL)

    acyclic = lax.cond(up_edge, _reaches_terminal,
                       lambda p: jnp.ones(M, bool), order_parent)
    valid = cascade_ok & acyclic & is_node_slot
    valid = valid.at[ROOT].set(True)
    # canonical parent pointer for existing nodes; root for itself
    parent_eff = jnp.where(valid, pslot, NULL).at[ROOT].set(ROOT)

    # ---- 7. Deletes: tombstone valid targets (first delete per target wins
    # the log; the tree flag is an idempotent OR either way).  Target match
    # checks the full claimed path exactly against the target's
    # materialised path.  Under the static no-deletes promise the whole
    # tombstone/dead machinery drops out of the trace.
    if no_deletes:
        # only these three escape the delete-guarded blocks
        deleted = jnp.zeros(M, bool)
        anc_del = jnp.full(M, IPOS, jnp.int32)
        dead = jnp.zeros(M, bool)
    else:
        _rows = part.gather_rows if part is not None \
            else (lambda t, i: t[i])
        d_depth_ok = (depth >= 1) & (depth <= D) & \
            (_rows(node_depth, d_tslot) == depth)
        paths_h, paths_l = _split_u(paths)   # per-op plane, elementwise
        d_path_ok = jnp.all(
            jnp.where(cols < depth[:, None],
                      (paths_h == _rows(fp_h, d_tslot)) &
                      (paths_l == _rows(fp_l, d_tslot)), True),
            axis=1)
        d_ok = is_del & d_tfound & (d_tslot != ROOT) & \
            _rows(valid, d_tslot) & d_depth_ok & d_path_ok
        d_tgt = jnp.where(d_ok, d_tslot, NULL)
        if part is not None:
            deleted = part.frame_reduce(
                M, 0, d_tgt, jnp.ones(N, jnp.int32), "max"
            ).astype(bool).at[NULL].set(False)
            del_pos = part.frame_reduce(M, IPOS, d_tgt, pos, "min") \
                .at[NULL].set(IPOS)
        else:
            deleted = jnp.zeros(M, bool).at[d_tgt].set(True) \
                .at[NULL].set(False)
            del_pos = jnp.full(M, IPOS, jnp.int32).at[d_tgt].min(pos) \
                .at[NULL].set(IPOS)

        # ---- 8. Dead-subtree propagation down tree-parent chains (delete
        # discards descendants, Internal/Node.elm:237-238).  Also carries
        # the earliest ancestor-delete position for absorption statuses.
        # Skipped when the batch has no effective delete.
        anc_del = jnp.where(_rows(deleted, parent_eff),
                            _rows(del_pos, parent_eff), IPOS)
        anc_del = _fix_min(anc_del, parent_eff, jnp.any(d_ok),
                           _ceil_log2(D) + 1)
        dead = valid & (anc_del < IPOS)

    # ---- 9. The order forest: each node's T* parent is the nearest node on
    # its within-branch anchor chain with a SMALLER timestamp (-1 = chain
    # exhausted at the branch head).  Slot ids compare like timestamps, so
    # the chase is pure int32.  Pointer-halving: when the current candidate
    # m has a larger slot than ours, everything m itself skipped is > m >
    # us, so jumping to m's own candidate skips no answer of ours.  On
    # causal logs anchors are older than their nodes (smaller ts) and the
    # loop exits in 0 trips.
    #
    # The chase alone is NOT enough: a walker crossing territory of
    # already-RESOLVED nodes advances one nearest-smaller step per trip
    # (resolved pointers are frozen answers, not skip pointers), so an
    # ascending anchor chain with a late smaller-ts op anchored at its
    # tail needs O(chain) trips — the trip cap would silently truncate
    # the walk and mis-parent the node (caught by the round-3 soak;
    # regression: tests/test_merge_kernel.py ascending-chain case).
    # Walkers still unresolved at the cap are finished EXACTLY by binary
    # lifting over the raw anchor pointers (ancestor jumps + path-min
    # tables, O(log^2) gathers) inside a lax.cond that causal and
    # descending-chain logs never take.
    in_forest = valid & is_node_slot
    mptr0 = jnp.where(node_anchor_is_sentinel | ~in_forest, -1, aslot)

    nsv_cap = _ceil_log2(M) + 2

    def nsv_cond(state):
        mptr, i = state
        return (i < nsv_cap) & jnp.any((mptr >= 0) & (mptr > slot_ids))

    def nsv_body(state):
        mptr, i = state
        m = jnp.where(mptr >= 0, mptr, NULL)
        unresolved = (mptr >= 0) & (mptr > slot_ids)
        return jnp.where(unresolved, mptr[m], mptr), i + 1

    mptr, _ = lax.while_loop(nsv_cond, nsv_body, (mptr0, jnp.int32(0)))
    nsa_unresolved = (mptr >= 0) & (mptr > slot_ids)

    def _nsa_lifting(mptr):
        # up[k][v] = 2^k-th anchor ancestor (ROOT-absorbing; ROOT's slot
        # 0 is smaller than every node, so it acts as the chain-exhausted
        # stop); mn[k][v] = min slot among v's first 2^k proper ancestors
        # — and since slots ARE the comparison keys, mn values are slots.
        up0 = jnp.where(mptr0 >= 0, mptr0, ROOT).astype(jnp.int32)
        up0 = up0.at[ROOT].set(ROOT)
        ups = [up0]
        mns = [up0]
        k_levels = _ceil_log2(M)
        for _ in range(1, k_levels):
            pu, pm = ups[-1], mns[-1]
            ups.append(pu[pu])
            mns.append(jnp.minimum(pm, pm[pu]))
        # descend: skip 2^k ancestors whenever none of them is smaller
        cur = slot_ids
        for k in reversed(range(k_levels)):
            skip = nsa_unresolved & (mns[k][cur] >= slot_ids)
            cur = jnp.where(skip, ups[k][cur], cur)
        ans = up0[cur]          # first ancestor below the walker's slot
        lifted = jnp.where(ans == ROOT, -1, ans)
        return jnp.where(nsa_unresolved, lifted, mptr)

    mptr = lax.cond(jnp.any(nsa_unresolved), _nsa_lifting,
                    lambda m: m, mptr)
    star_parent = jnp.where(mptr >= 0, mptr, pslot)
    star_sentinel = mptr < 0

    # Sibling sort → Euler-tour successor pointers.  Children of p: child-
    # branch T* roots first (group 0), then same-branch T* children (group
    # 1); each group timestamp-DESCENDING (the RGA rule: higher timestamp
    # closer to the anchor) — slot-descending, int32 keys only.
    #
    # The sort only has work to do at CROWDED parents (≥ 2 children):
    # a singleton child needs no ordering at all, and real op logs are
    # chain-dominated — almost every T* parent has exactly one child, so
    # the M-wide 3-key sort (the kernel's costliest stage once the
    # timestamp sort moved to ingest) would re-sort a million rows to
    # order a few dozen contested sibling groups.  Instead: count
    # children per parent (one scatter-add), compact the crowded rows by
    # prefix-sum, and sort only those at a small static width S_CAP,
    # falling back to the full-width sort when the batch is adversarially
    # contested (wide-fanout combs, descending rounds).  Both branches
    # produce identical (sib_next, first_child).
    order_parent = jnp.where(in_forest, star_parent, order_parent)
    order_parent = order_parent.at[ROOT].set(ROOT).at[NULL].set(NULL)
    ggrp = jnp.where(star_sentinel, 0, 1).astype(jnp.int8)

    def _sib_links(kp, gg, neg):
        """Sibling links from a 3-key sort at the input width; rows with
        ``neg == IPOS`` are padding (slot maps to M, scatters drop)."""
        s_parent, _, s_neg = lax.sort((kp, gg, neg), num_keys=3)
        s_slot = jnp.where(s_neg == IPOS, M, -s_neg)
        same_parent = (s_parent[1:] == s_parent[:-1]) & (s_slot[1:] < M)
        sib = jnp.full(M, -1, jnp.int32).at[s_slot[:-1]].set(
            jnp.where(same_parent, s_slot[1:], -1),
            mode="drop", unique_indices=True)
        s_start = jnp.concatenate([jnp.ones(1, bool), ~same_parent])
        fc_tgt = jnp.where(s_start & (s_slot < M), s_parent, M)
        fc = jnp.full(M, -1, jnp.int32).at[fc_tgt].set(
            s_slot, mode="drop", unique_indices=True)
        return sib, fc

    skey = jnp.where(in_forest, order_parent, NULL).astype(jnp.int32)
    neg_slot = jnp.where(in_forest, -slot_ids, IPOS)
    S_CAP = _env_cap("GRAFT_S_CAP", S_CAP_DEFAULT)
    # sibling-crowding pre-pass hint (ISSUE 13 satellite): for vouched
    # all-adds batches whose crowding structure the host derived AND
    # verified (codec/packed.derive_crowding_hints — all rows valid,
    # every anchor causally older), the crowded flags and their
    # compaction positions arrive as slot-space columns and the
    # scatter-add + gather + cumsum trio drops out of the trace
    # STATICALLY.  Gate mirrored by merge.crowding_hinted so the chain
    # auditor records which leg a trace runs.
    crowd_hinted = fused and no_deletes and \
        "crowd_slot" in ops and "crowd_cpos" in ops
    if S_CAP >= M:
        sib_next, first_child = _sib_links(skey, ggrp, neg_slot)
    else:
        par = jnp.where(in_forest, order_parent, M)
        if crowd_hinted:
            pad_f = jnp.zeros(1, bool)
            crowded = jnp.concatenate(
                [pad_f, ops["crowd_slot"].astype(bool), pad_f])
            cc = ops["crowd_cpos"].astype(jnp.int32)
            cpos = jnp.concatenate(
                [jnp.full(1, -1, jnp.int32), cc, cc[N - 1:N]])
            n_crowded = cc[N - 1] + 1
        else:
            if part is not None:
                cnt = part.frame_add(M, par)
                crowded = in_forest & (part.gather_rows(
                    cnt, jnp.minimum(par, M - 1)) >= 2)
                cpos = part.cumsum(crowded.astype(jnp.int32)) - 1
            else:
                cnt = jnp.zeros(M, jnp.int32).at[par].add(1, mode="drop")
                crowded = in_forest & (cnt[jnp.minimum(par, M - 1)] >= 2)
                cpos = lax.cumsum(crowded.astype(jnp.int32)) - 1
            n_crowded = cpos[M - 1] + 1

        def _br_compact(cap):
            """The compact sibling branch at static width ``cap``: the
            links are identical for ANY cap ≥ n_crowded (padding rows
            sort last and drop), so nested caps are pure speed tiers —
            XLA-CPU sort time and the unrolled binary search both scale
            with the static width."""
            def br(_):
                # scatter-free compaction: ``cpos`` is a nondecreasing
                # ±1-step cumsum, so the k-th crowded row is the first
                # index where it reaches k — a binary search per compact
                # slot (cap-wide, log M unrolled hops) followed by one
                # compact-width [cap, 2] row gather (key+group
                # bit-packed — skey ≤ NULL < 2^30); padding detection
                # is ``neg == IPOS``
                ks = jnp.arange(cap, dtype=cpos.dtype)
                src = jnp.searchsorted(
                    cpos, ks, side="left",
                    method="scan_unrolled").astype(jnp.int32)
                valid_k = ks < n_crowded
                srcc = jnp.minimum(src, M - 1)
                vals = jnp.stack(
                    [(skey << 1) | ggrp.astype(jnp.int32),
                     neg_slot], axis=-1)[srcc]
                kp = jnp.where(valid_k, vals[:, 0] >> 1, IPOS)
                gg = jnp.where(valid_k, vals[:, 0] & 1,
                               0).astype(jnp.int8)
                neg = jnp.where(valid_k, vals[:, 1], IPOS)
                sib, fc = _sib_links(kp, gg, neg)
                # singleton children: the parent's whole child list
                return sib, _fc_singletons(fc)
            return br

        def _fc_singletons(fc):
            """The singleton first-child overlay (every uncrowded
            parent's one child), part-routed when partitioned: each
            device scatters its ceil(M/k) pairs into a -1 frame and a
            pmax joins (targets unique — a parent is crowded xor
            singleton; values are slots ≥ 1)."""
            tgt = jnp.where(in_forest & ~crowded, order_parent, M)
            single_v = jnp.where(in_forest & ~crowded, slot_ids, M)
            val = jnp.where(single_v < M, single_v, -1)
            if part is not None:
                ov = part.frame_set(M, -1, tgt, val, "max")
                return jnp.where(ov >= 0, ov, fc)
            return fc.at[tgt].set(val, mode="drop", unique_indices=True)

        def br_single(_):
            """ALL crowded rows share one (parent, group) key — the flat
            concurrent-editor shape (every op a sibling under one
            anchor: adversarial configs 6/7 put ~1M rows here) — so the
            sorted order is analytically slot-DESCENDING and the links
            build with no sort, no scatter and no gather: each crowded
            slot's sib_next is the previous crowded slot (one running
            max), first_child of the one key is the largest crowded
            slot (a reduce)."""
            pc_src = jnp.where(crowded, slot_ids, -1)
            pc = part.cummax(pc_src) if part is not None \
                else lax.cummax(pc_src)
            prev = jnp.concatenate(
                [jnp.full(1, -1, jnp.int32), pc[:-1]])
            sib = jnp.where(crowded, prev, -1)
            head = jnp.max(jnp.where(crowded, slot_ids, -1))
            gkey = jnp.clip(jnp.max(jnp.where(crowded, skey, -1)),
                            0, M - 1)
            fc = jnp.full(M, -1, jnp.int32).at[gkey].set(head)
            return sib, _fc_singletons(fc)

        ckey = jnp.where(crowded, skey, IPOS)
        cgrp = jnp.where(crowded, ggrp.astype(jnp.int32), IPOS)
        one_group = (n_crowded > 0) & \
            (jnp.min(ckey) == jnp.max(jnp.where(crowded, skey, -1))) & \
            (jnp.min(cgrp) == jnp.max(jnp.where(
                crowded, ggrp.astype(jnp.int32), -1)))

        S_CAP2 = _env_cap("GRAFT_S_CAP2", S_CAP2_DEFAULT)

        def _compact_dispatch(_):
            full = lambda __: _sib_links(skey, ggrp, neg_slot)  # noqa: E731
            mid = lambda __: lax.cond(              # noqa: E731
                n_crowded <= S_CAP, _br_compact(S_CAP), full, None)
            if S_CAP2 < S_CAP:
                return lax.cond(n_crowded <= S_CAP2,
                                _br_compact(S_CAP2), mid, None)
            return mid(None)

        sib_next, first_child = lax.cond(
            one_group, br_single, _compact_dispatch, None)
    # the root never sits in a sibling list (its exit token is the chain
    # terminal below)
    sib_next = sib_next.at[ROOT].set(-1)
    first_child = first_child.at[NULL].set(-1)

    # ---- 10. Euler tour: enter(v) = token v, exit(v) = token M + v.
    # Successors form one chain per tree ending in the self-loop at
    # exit(root); tokens of parked (invalid) slots self-loop, and ADJACENT
    # self-looping tokens merge into one terminal zero-weight run below.
    #
    # LEAF EXITS ARE SKIPPED: exit tokens carry zero weight and ranks are
    # only ever read at enter tokens, so a leaf's enter jumps straight to
    # what its exit would target and the orphaned exit self-loops.  Suffix
    # weights along the chain are unchanged (the skipped token weighs 0);
    # what changes is run CONTRACTION on leaf-heavy tours: every
    # enter(leaf)→exit(leaf) alternation that ended a run disappears, so
    # chains whose leaves sit on slot-adjacent boundaries contract into
    # longer runs (descending-chains config 6: 694 → 562 ms CPU).  The
    # comb (bench/workloads.comb_pairs) stays the deliberate worst case:
    # it alternates SLOT halves (teeth upper, children lower), so its
    # enter half fragments regardless of exits and still takes the
    # full-width Wyllie fallback.
    T = 2 * M
    tok = jnp.arange(T, dtype=jnp.int32)
    in_tour = in_forest.at[ROOT].set(True)
    up = jnp.where(order_parent == slot_ids, M + slot_ids, M + order_parent)
    chain_next = jnp.where(sib_next >= 0, sib_next, up)
    is_leaf = first_child < 0
    enter_succ = jnp.where(
        ~in_tour, slot_ids,
        jnp.where(is_leaf, chain_next, first_child))
    exit_succ = jnp.where(
        ~in_tour | is_leaf, M + slot_ids, chain_next)
    succ = jnp.concatenate([enter_succ, exit_succ]).astype(jnp.int32)

    # ---- 11. Masks (the ranking below counts them as token weights).
    exists = valid & is_node_slot
    tomb = deleted & exists
    dead = dead & exists
    visible = exists & ~tomb & ~dead

    # ---- 12. Document ranks by run contraction + weighted Wyllie.
    # Maximal ±1-stride index runs of the tour chain occupy contiguous token
    # intervals (insertion chains make consecutive slots chain their tokens
    # consecutively), found elementwise; each contracts to one element of a
    # weighted list ranked by pointer doubling in O(log #runs) trips.
    # Ranks are computed directly as DENSE indices by weighting tokens with
    # what they count — existing-node enter tokens for document order,
    # visible-node enter tokens for the visible order — so no sort is
    # needed afterwards: rank(v) = (weight at or after enter(root)) -
    # (weight at or after enter(v)) = weighted count strictly before v.
    fwd = succ[:-1] == tok[1:]          # token j links to j+1
    bwd = succ[1:] == tok[:-1]          # token j+1 links to j
    # adjacent SELF-LOOPING tokens (parked slots, skipped leaf exits)
    # merge into one zero-weight terminal run instead of one singleton
    # run each — a comb's M orphaned leaf exits must not push n_runs
    # past R_CAP and re-trigger the very fallback the skip removes
    loop_ = succ == tok
    same_run = fwd | bwd | (loop_[:-1] & loop_[1:])
    boundary = jnp.concatenate([jnp.ones(1, bool), ~same_run])

    # Token weights and their exclusive prefix sums.  Only ENTER tokens
    # (the first M) carry weight — exit tokens count nothing — so the
    # prefix sums run at M+1 width and any token index x reads as
    # ``cse[min(x, M)]``.  No LINKED run straddles the enter/exit
    # boundary (token M-1 is the parked NULL slot's enter, token M the
    # terminal; neither links ±1); the one straddling run that CAN exist
    # is the merged self-loop block across M-1/M, which is terminal and
    # zero-weight — its window reads are clamped and then zeroed by
    # ``run_terminal`` in _expand, so the clamp never mis-weights it.
    #
    # Under the static no-deletes promise ``visible ≡ exists``, so the
    # visible lane of the whole rank pipeline is the doc lane — one
    # weight lane, single-column Wyllie, a [4, M] expansion plane, and
    # ``visible_order`` aliasing ``order`` (one fewer M-wide scatter).
    # With deletes both lanes ride.  The run-id prefix sum and the
    # weight lanes fuse into ONE pallas sequential-grid scan on TPU
    # (ops/tour_scan, T = 2M tokens + Kw·M weights in the same sweep);
    # elsewhere they are lax cumsums — bit-identical
    # (tests/test_tour_scan.py).
    w_lanes = jnp.stack(
        [exists.astype(jnp.int32)] if single_w else
        [exists.astype(jnp.int32), visible.astype(jnp.int32)])
    from . import tour_scan
    if part is not None:
        # ops-axis sharded: local ceil(M/k)-chunk scans + one fused
        # ring exchange of run-id/suffix-weight carries + local fixup
        # (ops/tour_scan.sharded_prefix_sums; exact by associativity)
        rid_incl, w_incl = part.prefix_sums(
            boundary.astype(jnp.int32), w_lanes)
    else:
        rid_incl, w_incl = tour_scan.prefix_sums(
            boundary.astype(jnp.int32), w_lanes, use_pallas)
    rid = rid_incl - 1                   # run id per token
    z1 = jnp.zeros(1, jnp.int32)
    cse_doc = jnp.concatenate([z1, w_incl[0]])
    cse_vis = cse_doc if single_w else jnp.concatenate([z1, w_incl[1]])

    def _runs_full():
        """T-wide run starts via the unique-set scatter (each run has
        exactly one start token); runs TILE the token axis contiguously
        (rid is a boundary cumsum), so each run ends where the next
        begins — run_e derives elementwise instead of paying a second
        M-wide scatter."""
        run_s = jnp.full(T, IPOS, jnp.int32).at[
            jnp.where(boundary, rid, T)].set(tok, mode="drop",
                                             unique_indices=True)
        next_s = jnp.concatenate([run_s[1:],
                                  jnp.full(1, IPOS, jnp.int32)])
        run_e = jnp.where(run_s == IPOS, 0,
                          jnp.where(next_s == IPOS, T - 1, next_s - 1))
        return run_s, run_e

    def _expand(run_s_w, run_e_w):
        """Per-run chain data at width ``run_s_w.shape[0]`` → Wyllie →
        the [7, M] token expansion (direction flag, weight-window
        bounds, suffix weights), via the monotone gather over rid[:M]
        (ranks are read only at ENTER tokens; rid[:M] < M since rid
        climbs by ≤ 1 from 0).  Direction: a run is forward when its
        start token links to start+1.  Linked runs never straddle the
        enter/exit boundary (token M-1 is the parked NULL slot's enter,
        token M the terminal, neither links ±1); merged SELF-LOOP blocks
        may straddle it, but they are terminal and zero-weight by
        construction — ``run_fwd`` is False for them (a self-loop never
        links +1) and ``run_terminal`` zeroes their weights, so every
        later change must preserve exactly that pair of facts."""
        w = run_s_w.shape[0]
        run_fwd = succ[jnp.minimum(run_s_w, T - 1)] == run_s_w + 1
        run_tail = jnp.where(run_fwd, run_e_w, run_s_w)
        tail_succ = succ[jnp.minimum(run_tail, T - 1)]
        run_terminal = tail_succ == run_tail
        rid_of = lambda x: rid[jnp.minimum(x, T - 1)]  # noqa: E731
        run_next = jnp.where(run_terminal, rid_of(run_tail),
                             rid_of(tail_succ))
        run_s_c = jnp.minimum(run_s_w, M)
        run_e1_c = jnp.minimum(run_e_w + 1, M)
        # per-run total weight; zero-weight absorbing (terminal) runs
        # make the Wyllie telescoping exact once pointers collapse.
        # single_w: the visible lane IS the doc lane (no-deletes), so
        # the doubling loop and the expansion plane carry one column
        a0 = jnp.where(run_terminal, 0, cse_doc[run_e1_c] - cse_doc[run_s_c])
        b0 = None if single_w else \
            jnp.where(run_terminal, 0, cse_vis[run_e1_c] - cse_vis[run_s_c])

        def wy_cond(state):
            live, i = state[-2], state[-1]
            return live & (i < _ceil_log2(w) + 1)

        def wy_body(state):
            if single_w:
                a, p, _, i = state
                p2 = p[p]
                return a + a[p], p2, jnp.any(p2 != p), i + 1
            a, b, p, _, i = state
            a2 = a + a[p]
            b2 = b + b[p]
            p2 = p[p]
            return a2, b2, p2, jnp.any(p2 != p), i + 1

        p0 = jnp.minimum(run_next, w - 1)
        if single_w:
            a_doc, _, _, _ = lax.while_loop(
                wy_cond, wy_body, (a0, p0, jnp.array(True), jnp.int32(0)))
            a_vis = None
        else:
            a_doc, a_vis, _, _, _ = lax.while_loop(
                wy_cond, wy_body,
                (a0, b0, p0, jnp.array(True), jnp.int32(0)))
        # rid[:M] < M, so the value plane never needs more than the
        # first M runs — slice full-width (w = 2M) fallback sources down
        out = min(w, M)
        per_run = jnp.stack([
            run_fwd[:out].astype(jnp.int32),
            cse_doc[run_s_c[:out]], cse_doc[run_e1_c[:out]], a_doc[:out],
        ] + ([] if single_w else [
            cse_vis[run_s_c[:out]], cse_vis[run_e1_c[:out]], a_vis[:out],
        ]))
        if part is not None:
            return part.mono_expand(per_run, rid[:M])
        return mono_gather.monotone_gather(per_run, rid[:M],
                                           use_pallas=use_pallas)

    # Per-run data live in the first #runs entries.  On real logs
    # #runs << T (insertion chains contract to a handful of runs each),
    # so the whole per-run pipeline — derivation gathers, the doubling
    # loop, the expansion-source build, and the monotone gather's value
    # plane — runs at a small static width R_CAP whenever the run count
    # fits, falling back to full width for adversarially fragmented
    # tours (comb-shaped logs where every token is its own run).  Both
    # branches produce the same [7, M] expansion.
    R_CAP = _env_cap("GRAFT_R_CAP", R_CAP_DEFAULT)
    if R_CAP >= T:
        ex = _expand(*_runs_full())
    else:
        # scatter-free run starts on the compact path: rid is a
        # nondecreasing boundary cumsum hitting every id 0..n_runs-1,
        # so run k's first token is a binary search — R_CAP-wide,
        # log T unrolled hops — and the T-wide-index scatter survives
        # only in the fragmented-tour fallback branch (XLA-CPU
        # serializes scatters; this one was the single most expensive
        # op of the CPU fallback bench)
        n_runs = rid[T - 1] + 1

        def _compact(cap):
            """Scatter-free run pipeline at static width ``cap`` —
            identical expansion for any cap ≥ n_runs (unused run ids
            read IPOS starts exactly as the scatter version's defaults),
            so nested caps are pure speed tiers."""
            def br(_):
                ks = jnp.arange(cap, dtype=jnp.int32)
                ss = jnp.searchsorted(
                    rid, ks, side="left",
                    method="scan_unrolled").astype(jnp.int32)
                run_s_w = jnp.where(ks < n_runs, ss, IPOS)
                next_s = jnp.concatenate([run_s_w[1:],
                                          jnp.full(1, IPOS, jnp.int32)])
                run_e_w = jnp.where(run_s_w == IPOS, 0,
                                    jnp.where(next_s == IPOS, T - 1,
                                              next_s - 1))
                return _expand(run_s_w, run_e_w)
            return br

        R_CAP2 = _env_cap("GRAFT_R_CAP2", R_CAP2_DEFAULT)
        mid = lambda _: lax.cond(               # noqa: E731
            n_runs <= R_CAP, _compact(R_CAP),
            lambda __: _expand(*_runs_full()), None)
        if R_CAP2 < R_CAP:
            ex = lax.cond(n_runs <= R_CAP2, _compact(R_CAP2), mid, None)
        else:
            ex = mid(None)

    # E(tok) = weight at-or-after tok along the chain; within-run
    # offsets from the global cumsum (forward runs count from the run
    # start, backward runs toward it); ranks then read at ENTER tokens
    # (tokens 0..M-1) — half the tour.
    rf_m = ex[0].astype(bool)

    def rank_of(ws_m, we1_m, a_m, cse):
        # enter tokens are 0..M-1, so cse[tok] and cse[tok+1] slice clean
        within = jnp.where(rf_m, cse[:M] - ws_m, we1_m - cse[1:M + 1])
        e_tok = a_m - within
        return e_tok[ROOT] - e_tok

    doc_dense = rank_of(ex[1], ex[2], ex[3], cse_doc)

    doc_index = jnp.where(exists, doc_dense, IPOS)

    def _order_frame(mask, dense):
        """Rank→slot frame scatter, part-routed when partitioned (ranks
        are globally unique and slots < NULL, so per-device scatters
        join exactly under pmin)."""
        tgt = jnp.where(mask, dense, M)
        if part is not None:
            return part.frame_set(M, NULL, tgt, slot_ids, "min")
        return jnp.full(M, NULL, jnp.int32).at[tgt].set(
            slot_ids, mode="drop", unique_indices=True)

    order = _order_frame(exists, doc_dense)
    if single_w:
        # no deletes ⇒ visible ≡ exists ⇒ the visible order IS the
        # document order, statically — the second rank expansion and
        # its M-wide scatter drop out of the trace
        visible_order = order
    else:
        vis_dense = rank_of(ex[4], ex[5], ex[6], cse_vis)
        visible_order = _order_frame(visible, vis_dense)

    # ---- 13. Sequential-parity statuses per op.  Per-slot facts pack
    # into one int32 so each op needs two gathers (meta + anc_del), not
    # five separate ones.
    status = jnp.full(N, PAD, jnp.int8)
    a_slot = op_slot
    _prow = part.gather_rows if part is not None \
        else (lambda t, i: t[i])
    # an Add with ts 0 collides with the branch-head sentinel: the reference
    # finds an existing child and reports AlreadyApplied
    a_sentinel = ts <= 0
    if no_deletes:
        # grandvalid (valid[pslot], the NOT_FOUND/INVALID_PATH split) is
        # only read for INVALID non-sentinel adds — on the production
        # all-valid path that M-wide gather pair moves inside a cond
        # the fast path never takes; the always-paid cost is the one
        # per-op meta gather below
        meta_s = valid.astype(jnp.int32) | \
            (parent_ok.astype(jnp.int32) << 1)
        a_meta = _prow(meta_s, a_slot)
        a_valid = (a_meta & 1) != 0
        a_parent_ok = (a_meta & 2) != 0

        def _status_slow(_):
            a_grand = valid[pslot][a_slot]   # valid[pslot[a_slot]]
            return jnp.where(
                a_sentinel | (a_valid & op_is_dup), ALREADY_APPLIED,
                jnp.where(a_valid, APPLIED,
                          jnp.where(a_parent_ok & a_grand, NOT_FOUND,
                                    INVALID_PATH))).astype(jnp.int8)

        def _status_fast(_):
            # every non-sentinel add is valid here, so only the
            # duplicate/sentinel split remains — same formula with the
            # never-selected invalid arm dropped
            return jnp.where(a_sentinel | (a_valid & op_is_dup),
                             ALREADY_APPLIED,
                             APPLIED).astype(jnp.int8)

        need_grand = jnp.any(is_add & ~a_sentinel & ~a_valid)
        a_status = lax.cond(need_grand, _status_slow, _status_fast, None)
        status = jnp.where(is_add, a_status, status)
    else:
        meta = (valid.astype(jnp.int32)
                | (parent_ok.astype(jnp.int32) << 1)
                | (_prow(valid, pslot).astype(jnp.int32) << 2))
        a_meta = _prow(meta, a_slot)
        a_valid = (a_meta & 1) != 0
        a_parent_ok = (a_meta & 2) != 0
        a_grandvalid = (a_meta & 4) != 0     # valid[pslot[a_slot]]
        a_absorbed = a_valid & (_prow(anc_del, a_slot) < pos)
        a_status = jnp.where(
            a_sentinel | (a_valid & (op_is_dup | a_absorbed)),
            ALREADY_APPLIED,
            jnp.where(a_valid, APPLIED,
                      jnp.where(a_parent_ok & a_grandvalid, NOT_FOUND,
                                INVALID_PATH)))
        status = jnp.where(is_add, a_status.astype(jnp.int8), status)
    # deletes (statically absent under the no-deletes promise)
    if not no_deletes:
        d_parent_ok = (depth == 1) | \
            ((depth >= 2) & dp_found & ((_prow(meta, dp_slot) & 1) != 0))
        d_anc_absorbed = d_ok & (_prow(anc_del, d_tslot) < pos)
        d_repeat = d_ok & (_prow(del_pos, d_tslot) < pos)
        d_target_later = d_ok & (_prow(node_pos, d_tslot) > pos)
        # deleting a branch-head sentinel (ts 0) finds a tombstone:
        # AlreadyApplied
        d_sentinel = (ts == 0) & d_parent_ok
        d_status = jnp.where(
            d_sentinel | d_anc_absorbed | (d_repeat & ~d_target_later),
            ALREADY_APPLIED,
            jnp.where(d_ok & ~d_target_later, APPLIED,
                      jnp.where(d_target_later | d_parent_ok, NOT_FOUND,
                                INVALID_PATH)))
        status = jnp.where(is_del, d_status.astype(jnp.int8), status)

    return NodeTable(
        ts=node_ts, parent=parent_eff, depth=node_depth,
        value_ref=node_value_ref, paths=_pack_u(fp_h, fp_l),
        exists=exists, tombstone=tomb,
        dead=dead, visible=visible, doc_index=doc_index, order=order,
        visible_order=visible_order,
        num_nodes=jnp.sum(exists).astype(jnp.int32),
        num_visible=jnp.sum(visible).astype(jnp.int32),
        status=status)

def host_no_deletes(kind) -> bool:
    """Host-side check backing the kernel's static no-deletes promise —
    the single definition of "this batch has no delete-like rows"; every
    caller that sets the static flag must use it (a violated promise
    silently drops deletes).  Only a host-resident column can be checked
    without a device sync; anything else conservatively returns False."""
    return isinstance(kind, np.ndarray) and \
        not bool(np.any(kind == KIND_DELETE))


def materialize(ops: Dict[str, jax.Array],
                use_pallas: Optional[bool] = None,
                hints: Optional[str] = None,
                interpret: bool = False) -> NodeTable:
    """ops arrays (see codec.packed.PackedOps.arrays) → NodeTable.

    ``interpret=True`` runs every pallas kernel of the trace through the
    pallas interpreter (CPU tests of the kernel path).

    Host-resident kind columns are checked once so all-adds batches take
    the leaner static no-deletes trace (see ``_materialize``).

    Timestamps are int64, so the kernel requires 64-bit mode; if the host
    program runs JAX in default x32 mode, tracing and input conversion are
    scoped inside ``jax.enable_x64`` rather than flipping the process-global
    flag.
    """
    no_deletes = host_no_deletes(ops.get("kind"))
    if interpret:
        use_pallas = "interpret"
    if jax.config.jax_enable_x64:
        return _materialize(ops, use_pallas, hints, no_deletes)
    with jax.enable_x64(True):
        return _materialize(ops, use_pallas, hints, no_deletes)
