"""Explicitly partitioned merge: per-shard local work + named collectives.

SURVEY §7 step 4 asks for genuinely partitioned joins with explicit
boundary exchange — the ICI answer to the reference's application network
(ops ship between replicas as JSON; here shards of one op batch exchange
summaries over the mesh).  ``parallel/mesh.py`` delegates partitioning to
XLA (whole-array kernel + input shardings); this module instead expresses
the resolution stages (slot assignment, duplicate election, timestamp→slot
reference resolution, hint verification) as ``jax.shard_map`` with the
communication written out:

- **local slot scatter + min all-reduce**: each shard scatters its ops'
  global row indices into an M-wide winner frame (slot = ingest
  rank + 1), and ONE ``lax.pmin`` joins the frames — the semilattice
  join of partial node tables, 4·M bytes/device ring traffic.  Every
  other node column then derives by gathering the winning row's fields
  from the gathered summaries (the stock ranked path's one-scatter
  construction; scatters carry a large fixed per-element cost on v5e).
- **shard-summary all-gather**: link hints are GLOBAL row positions, so
  resolving a cross-shard reference needs the referenced row's
  (ts, is_add, slot) — exactly the "boundary exchange of shard
  summaries": one tiled ``lax.all_gather`` of the 13-byte/op summary
  columns, after which every resolution gather is local.
- **replicated tail**: the downstream stages (validity cascade, tombstone
  propagation, Euler tour, run-contracted ranking — merge._finish) run
  replicated on every device from the reduced node frame.  This is a
  MEASURED trade, not a guess (VERDICT r4 next-3; updated with the r5
  on-chip shares and the post-rewrite CPU-proxy split, tail ≈ 76% ⇒
  single-merge ceiling ~1.3–1.6× on 8 chips): the fully sharded tail
  is designed (segmented-scan rid, searchsorted compaction, replicated
  ≤32k-wide Wyllie core) with a ~4× Amdahl ceiling, and the docs axis
  delivers 8× today — data, model, design and the committed conclusion
  live in docs/SHARD_TAIL.md (§2b for round 5), instruments in
  scripts/probe_stages.py (kernel ``probe=`` cuts) and
  scripts/probe_shard_stages.py.
  The full op columns are all-gathered once inside the shard_map (the
  tail needs them for the path-plane scatter; doing it explicitly keeps
  the collective schedule visible and measurable).

The whole-array kernel remains the reference path; this path is pinned
bit-identical to it (tests/test_shard_map.py) and its collective volume
is measured against XLA's auto-partitioning of the same merge
(``collective_bytes``; artifact in the round sweep file).

Fallback semantics match the stock kernel: in auto mode the rank/link
verification runs distributed (violation counts psum-reduced), and a
failed verification routes the GATHERED batch through the shared
``merge._resolve_sorted`` under a replicated ``lax.cond`` — wrong hints
cost speed, never correctness.

Pallas note: the rank-expansion gather (ops/mono_gather.py) runs inside
the replicated tail, where every operand is fully replicated — the SPMD
partitioner does not need to slice through the Mosaic call, so
``use_pallas`` may be left on auto here (unlike mesh.py's input-sharded
whole-array path, where the pallas call would sit astride a partitioned
axis and is pinned off).  CPU-mesh tests exercise the lax path; the
Mosaic path under a real multi-chip mesh is untested until multi-chip
hardware exists (single-chip TPU runs never shard).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..codec.packed import KIND_ADD, KIND_DELETE
from ..ops import merge as merge_mod
from ..ops.merge import BIG, IPOS, NodeTable
from .mesh import OPS_AXIS, _pad_ops_to, round_up

# op columns crossing the shard_map boundary, in positional order
_COLS = ("kind", "ts", "parent_ts", "anchor_ts", "depth", "paths",
         "value_ref", "pos", "parent_pos", "anchor_pos", "target_pos",
         "ts_rank")


def _resolve_local(N: int, M: int, vouched: bool, *cols):
    """Per-shard body: local resolution + explicit collectives.

    Runs under shard_map with every input sliced along the op axis
    (length N/k rows here); every output is REPLICATED (identical on
    all devices) — node frames by min all-reduce, per-op columns by
    tiled all-gather.  ``N``/``M`` are the GLOBAL widths; ``vouched``
    mirrors the stock kernel's exhaustive mode (skip the per-hint ts
    check gathers, merge._res_hint_impl)."""
    (kind, ts, parent_ts, anchor_ts, depth, paths, value_ref, pos,
     parent_pos, anchor_pos, target_pos, ts_rank) = cols
    ROOT, NULL = 0, M - 1
    n_loc = kind.shape[0]
    ts = ts.astype(jnp.int64)
    rank = ts_rank.astype(jnp.int32)
    is_add = kind == KIND_ADD
    is_del = kind == KIND_DELETE
    row = (lax.axis_index(OPS_AXIS) * n_loc +
           jnp.arange(n_loc, dtype=jnp.int32))   # global array row

    # ---- slot assignment: local elementwise (rank hints are global)
    is_real_add = is_add & (ts > 0) & (ts < BIG)
    has_rank = is_real_add & (rank >= 0) & (rank < N)
    op_slot = jnp.where(has_rank, rank + 1, NULL).astype(jnp.int32)

    # ---- duplicate election: local M-frame scatter-min of the global
    # row index, joined by one ring min-reduce (the first five tuple
    # entries of the kernel's resolution interface come from these
    # frames).  Winner rule = min array row, identical to the stock
    # ranked path and the stable sort.
    tgt = jnp.where(has_rank, op_slot, M)
    win = jnp.full(M, IPOS, jnp.int32).at[tgt].min(row, mode="drop")
    win = lax.pmin(win, OPS_AXIS)
    is_canon = has_rank & (row == win[op_slot])
    op_is_dup = has_rank & ~is_canon

    # ---- boundary exchange: the shard summary every other shard needs
    # to answer timestamp references into this shard (hint columns hold
    # GLOBAL rows).  12 bytes/op, one tiled all-gather (is_add and
    # op_slot travel pre-fused, merge._pack_slot_or_neg); all resolution
    # gathers below are then local.
    ts_g = lax.all_gather(ts, OPS_AXIS, tiled=True)
    son_g = lax.all_gather(
        merge_mod._pack_slot_or_neg(is_add, op_slot), OPS_AXIS,
        tiled=True)

    # node frame: the joined win row IS the whole frame — every other
    # node column derives by gathering the canonical row's fields from
    # the gathered summary (merge._node_cols_from_row, the stock ranked
    # path's one-scatter construction).  A slot is used iff some row won
    # it; no op scatters to ROOT/NULL (slot = rank+1 ∈ [1, N]).
    pos_g = lax.all_gather(pos.astype(jnp.int32), OPS_AXIS, tiled=True)
    is_node_slot, node_ts, node_pos = merge_mod._node_cols_from_row(
        win, ts_g, pos_g, M, ROOT, N)

    res = functools.partial(merge_mod._res_hint_impl, slot_or_neg=son_g,
                            ts=ts_g, N=N, ROOT=ROOT, NULL=NULL,
                            check_ts=not vouched)
    pp_slot, pp_found, pp_miss = res(
        parent_pos.astype(jnp.int32), parent_ts.astype(jnp.int64))
    # fused anchor-or-target resolution (merge._join_ops_impl): anchor
    # for Add rows, delete target for Delete rows — consumed at disjoint
    # row sets by the tail, so one resolution (and one all-gather pair
    # below) serves both
    at_slot, at_found, at_miss = res(
        jnp.where(is_add, anchor_pos.astype(jnp.int32),
                  target_pos.astype(jnp.int32)),
        merge_mod._at_ts(is_add, anchor_ts.astype(jnp.int64), ts))

    # ---- distributed rank/link verification (the stock kernel's auto
    # mode, violation counts joined by psum): node-frame properties are
    # replicated after the reduces, per-op properties verify locally.
    used = is_node_slot
    dense_ok = jnp.all(~used[2:M - 1] | used[1:M - 2])
    incr_ok = jnp.all(jnp.where(used[1:M - 1] & used[2:M],
                                node_ts[1:M - 1] < node_ts[2:M], True))
    ts_match_l = jnp.all(
        jnp.where(has_rank, node_ts[jnp.clip(op_slot, 0, M - 1)] == ts,
                  True))
    all_ranked_l = jnp.all(~is_real_add | has_rank)
    link_miss_l = jnp.any(pp_miss) | \
        jnp.any(at_miss & (is_add | is_del))
    viol = (~ts_match_l).astype(jnp.int32) + \
        (~all_ranked_l).astype(jnp.int32) + link_miss_l.astype(jnp.int32)
    hints_ok = dense_ok & incr_ok & (lax.psum(viol, OPS_AXIS) == 0)

    # ---- assemble replicated outputs: per-op resolution columns and
    # the full op columns the replicated tail consumes (one explicit
    # all-gather each — this is where auto-partitioning would have
    # inserted its own gathers around the tail's scatters)
    gath = lambda x: lax.all_gather(x, OPS_AXIS, tiled=True)  # noqa: E731
    # global op_slot column, recovered elementwise from the fused
    # slot-or-neg summary (non-Add rows carried op_slot == NULL locally)
    op_slot_g = jnp.where(son_g >= 0, son_g, NULL).astype(jnp.int32)
    sel = (op_slot_g, gath(op_is_dup), node_ts, node_pos,
           is_node_slot, win, gath(pp_slot), gath(at_slot),
           gath(pp_found), gath(at_found))
    gathered = {
        "kind": gath(kind), "ts": ts_g,
        "parent_ts": gath(parent_ts), "anchor_ts": gath(anchor_ts),
        "depth": gath(depth), "paths": gath(paths),
        "value_ref": gath(value_ref), "pos": pos_g,
    }
    return gathered, sel, hints_ok


@functools.partial(jax.jit,
                   static_argnames=("mesh", "hints", "use_pallas",
                                    "no_deletes"))
def _shard_materialize_jit(device_ops, mesh: Mesh, hints: str,
                           use_pallas, no_deletes: bool) -> NodeTable:
    N = device_ops["kind"].shape[0]
    M = N + 2
    body = functools.partial(_resolve_local, N, M,
                             hints == "exhaustive")
    spec = [P(OPS_AXIS) if device_ops[c].ndim == 1 else P(OPS_AXIS, None)
            for c in _COLS]
    resolve = jax.shard_map(body, mesh=mesh, in_specs=tuple(spec),
                            out_specs=P(), check_vma=False)
    gathered, sel, hints_ok = resolve(*[device_ops[c] for c in _COLS])
    if hints == "exhaustive":
        pass          # caller vouched: the cond (and the sort) never trace
    else:
        sel = lax.cond(hints_ok, lambda _: sel,
                       lambda _: merge_mod._resolve_sorted(gathered), None)
    return merge_mod._finish(gathered, sel, use_pallas, no_deletes)


def shard_materialize(ops: Dict[str, np.ndarray], mesh: Mesh,
                      hints: str = "auto",
                      use_pallas=None) -> NodeTable:
    """One merge with the resolution stages explicitly partitioned over
    the mesh's ``ops`` axis (module docstring).  Requires the hint
    columns (any PackedOps has them); result is replicated and
    bit-identical to ``merge.materialize`` on the same ops."""
    if hints not in ("auto", "exhaustive"):
        raise ValueError(f"hints must be 'auto' or 'exhaustive', "
                         f"got {hints!r}")
    missing = [c for c in _COLS if c not in ops]
    if missing:
        raise ValueError(f"shard_materialize needs hint columns; "
                         f"missing {missing} (use packed.pack)")
    k = mesh.shape[OPS_AXIS]
    n = round_up(ops["kind"].shape[0], k)
    padded = _pad_ops_to(ops, n)
    no_deletes = merge_mod.host_no_deletes(np.asarray(ops["kind"]))

    def run():
        device_ops = {
            c: jax.device_put(
                padded[c],
                NamedSharding(mesh, P(OPS_AXIS) if padded[c].ndim == 1
                              else P(OPS_AXIS, None)))
            for c in _COLS}
        return _shard_materialize_jit(device_ops, mesh, hints,
                                      use_pallas, no_deletes)

    if jax.config.jax_enable_x64:
        return run()
    with jax.enable_x64(True):
        return run()


# ---- collective-volume accounting --------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}

_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
                "collective-permute", "reduce-scatter")


def _shape_bytes(shape: str) -> int:
    """Bytes of one HLO shape string like ``s32[8,131072]{1,0}``."""
    dt = shape.split("[", 1)[0]
    if dt not in _DTYPE_BYTES:
        return 0
    dims = shape.split("[", 1)[1].split("]", 1)[0]
    total = _DTYPE_BYTES[dt]
    for d in dims.split(","):
        d = d.strip()
        if d:
            total *= int(d)
    return total


def collective_stats(hlo_text: str) -> Dict[str, int]:
    """Per-collective output bytes summed from compiled HLO text — the
    measurable 'bytes moved' comparison between this module's explicit
    schedule and XLA's auto-partitioning (VERDICT r3 missing-2)."""
    import re
    out = {name: 0 for name in _COLLECTIVES}
    out["count"] = 0
    pat = re.compile(
        r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s+(" + "|".join(_COLLECTIVES) +
        r")(-start|-done)?\(")
    for m in pat.finditer(hlo_text):
        shapes, name, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue           # the -start already counted this transfer
        found = re.findall(r"[a-z0-9]+\[[0-9, ]*\]", shapes)
        if phase == "-start" and len(found) > 1:
            # async tuple is (operand alias, result, ...): count only
            # the transferred result, not the aliased operand
            found = found[1:]
        out[name] += sum(_shape_bytes(s) for s in found)
        out["count"] += 1
    out["total_bytes"] = sum(out[n] for n in _COLLECTIVES)
    return out


def measure_collectives(fn, *args) -> Dict[str, int]:
    """Compile ``fn(*args)`` and account its collective traffic."""
    return collective_stats(jax.jit(fn).lower(*args).compile().as_text())
