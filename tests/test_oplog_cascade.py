"""The cascade op-log (crdt_graph_tpu/oplog.py rebuild, ISSUE 8):
tiered hot-tail → packed-npz cold segments → checkpoint base, with
reference-stable ``operationsSince`` windows and watermark-gated GC.

The contract under test: the tiers are PHYSICAL only.  Every read —
object iteration, ``operations_since`` suffixes, bounded anti-entropy
windows (bytes AND ``X-Since-*`` meta), fingerprints, checkpoints —
must be indistinguishable from the untiered log across every tier
seam, while resident memory stays O(hot window) and a concurrent
spill/compaction/GC can never disturb an in-flight window chain.
"""
import io
import json
import os
import threading
import time
from http.client import HTTPConnection

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu import engine
from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.codec import packed as packed_mod
from crdt_graph_tpu.core import operation as op_mod
from crdt_graph_tpu.core.errors import CheckpointError
from crdt_graph_tpu.core.operation import Add, Batch, Delete
from crdt_graph_tpu.obs import prom as prom_mod
from crdt_graph_tpu.oplog import OpLog
from crdt_graph_tpu.serve import snapshot as snapshot_mod


def ts(r, c):
    return r * 2**32 + c


def chain_ops(r, n, start=1):
    out = []
    prev = ts(r, start - 1) if start > 1 else 0
    for c in range(start, start + n):
        out.append(Add(ts(r, c), (prev,), f"v{r}.{c}"))
        prev = ts(r, c)
    return out


def mixed_ops(n_per=40):
    """Two interleaved replica chains + scattered deletes + an
    ALL-DELETE TAIL — the window-rule torture shape (trim-to-Add,
    all-delete extension, inclusive terminator, delete-tail rule)."""
    a, b = chain_ops(1, n_per), chain_ops(2, n_per)
    ops = [op for pair in zip(a, b) for op in pair]
    # a delete burst mid-log (longer than small window limits)
    ops[n_per:n_per] = [Delete((ts(1, c),)) for c in range(3, 9)]
    # all-delete tail
    ops.extend(Delete((ts(2, c),)) for c in range(n_per - 4, n_per + 1))
    return ops


def applied_log_tree(ops):
    """Apply ``ops`` one-by-one through a reference tree so the log is
    a genuine applied history (deletes validated)."""
    t = engine.init(0)
    for op in ops:
        t.apply(op)
    return t


def tiered_copy(log_ops, tmp_path, name, **kw):
    """An OpLog holding ``log_ops`` with tiering armed and fully
    spilled under the given hot budget (folding disabled by default so
    tests see a multi-segment cold tier; pass gc_min_segs to allow
    compaction)."""
    kw.setdefault("hot_ops", 16)
    kw.setdefault("gc_min_segs", 99)
    log = OpLog(log_ops)
    log.enable_tiering(str(tmp_path / name), **kw)
    log.maybe_spill()
    return log


# -- logical equivalence across tiers ---------------------------------------


def test_tiered_log_matches_untiered_object_contract(tmp_path):
    ops = mixed_ops(30)
    t = applied_log_tree(ops)
    applied = list(t._log)
    flat = OpLog(applied)
    log = tiered_copy(applied, tmp_path, "eq")
    assert log.spills >= 1 and log.telemetry()["segments"]["cold"] >= 1
    assert len(log) == len(flat)
    assert list(log) == applied
    assert log[5] == applied[5]
    assert log[-1] == applied[-1]
    assert log[3:17] == applied[3:17]
    for op in applied:
        if isinstance(op, Add):
            assert log.index_of_add(op.ts) == flat.index_of_add(op.ts)
    assert log.index_of_add(ts(9, 9)) is None
    # full-column reassembly equals the untiered export
    a = log.to_packed(max_depth=4)
    b = flat.to_packed(max_depth=4)
    assert a.num_ops == b.num_ops
    assert packed_mod.unpack(a) == packed_mod.unpack(b)
    assert packed_mod.verify_hints(a)


def test_window_bytes_and_meta_identical_at_every_seam(tmp_path):
    """`packed_since_window` equivalence: for EVERY Add terminator and
    a spread of limits, the tiered view's window must be byte- and
    meta-identical to the untiered implementation — including windows
    that end exactly on a tier seam, all-delete windows that extend
    across a seam, and the all-delete-tail rule."""
    ops = mixed_ops(30)
    t = applied_log_tree(ops)
    applied = list(t._log)
    p = packed_mod.pack(applied, max_depth=4)
    # hot_ops=16 → several cold segments; limits chosen to land
    # windows exactly on 16-aligned seams as well as off them
    log = tiered_copy(applied, tmp_path, "seams", hot_ops=16)
    tele = log.telemetry()
    assert tele["segments"]["cold"] >= 3
    view = log.view(max_depth=4)
    adds = [op.ts for op in applied if isinstance(op, Add)]
    # every 2nd Add terminator (plus 0) × limits spanning sub-seam,
    # seam-exact (16 = the spill segment size), and cross-seam sizes —
    # tier-1-sized without losing any seam class
    boundaries = [0] + adds[::2] + adds[-2:]
    for since in boundaries:
        for limit in (0, 1, 3, 5, 8, 16, 1000):
            want = engine.packed_since_window(p, since, limit)
            got = view.window(since, limit)
            assert got[0] == want[0], (since, limit)
            assert got[1] == want[1], (since, limit)
    # unknown terminator: found=0, not a silent full pull
    _, meta = view.window(ts(7, 1), 4)
    assert not meta["found"]
    # unbounded suffix bytes match too
    for since in boundaries:
        assert view.since_bytes(since) == \
            engine.packed_since_bytes(p, since), since


def test_operations_since_equivalent_on_tiered_tree(tmp_path):
    ops = mixed_ops(25)
    plain = applied_log_tree(ops)
    tiered = applied_log_tree(ops)
    tiered.enable_log_tiering(str(tmp_path / "t"), hot_ops=16,
                              gc_min_segs=2)
    tiered._log.maybe_spill()
    assert tiered._log.spills >= 1
    applied_adds = [op.ts for op in list(plain._log)
                    if isinstance(op, Add)]
    for boundary in [0] + applied_adds[::3] + applied_adds[-2:]:
        assert tiered.operations_since(boundary) == \
            plain.operations_since(boundary), boundary
    # and the tree still merges correctly after the spill (cold tiers
    # reassemble into the kernel's candidate set)
    more = chain_ops(3, 1200)
    tiered.apply_packed(packed_mod.pack(more, max_depth=4))
    plain.apply(op_mod.from_list(more))
    assert tiered.visible_values() == plain.visible_values()
    assert tiered._replicas == plain._replicas


def test_window_chain_stable_across_concurrent_spill_and_gc(tmp_path):
    """A spill/compaction/GC landing BETWEEN the pulls of an in-flight
    anti-entropy chain must not shift, re-serve, or lose a window: the
    chain keeps reading from its pinned reference-stable view, and GC
    defers deleting any file that view still needs."""
    ops = mixed_ops(30)
    t = applied_log_tree(ops)
    applied = list(t._log)
    p = packed_mod.pack(applied, max_depth=4)
    log = tiered_copy(applied, tmp_path, "race", hot_ops=16,
                      gc_min_segs=2)
    view = log.view(max_depth=4)    # the chain's pinned view

    # expected chain against the untiered packing, precomputed
    def pull_chain(windows_fn):
        since, out = 0, []
        for _ in range(80):
            body, meta = windows_fn(since)
            out.append((body, tuple(sorted(meta.items()))))
            if meta["next_since"] is not None:
                since = meta["next_since"]
            if not meta["more"]:
                return out
        raise AssertionError("chain did not terminate")

    want = pull_chain(lambda s: engine.packed_since_window(p, s, 7))

    got = []
    since = 0
    step = 0
    while True:
        body, meta = view.window(since, 7)
        got.append((body, tuple(sorted(meta.items()))))
        # chaos between pulls: new writes + spill + watermark GC
        log.extend(chain_ops(5, 3, start=1 + 3 * step))
        log.maybe_spill()
        log.run_gc()
        step += 1
        if meta["next_since"] is not None:
            since = meta["next_since"]
        if not meta["more"]:
            break
    assert got == want
    # a FRESH view serves the grown log (old ops + the chaos writes)
    n_new = len(log)
    assert n_new == len(applied) + 3 * step
    fresh = log.view(max_depth=4)
    assert fresh.length == n_new
    # dropping the pinned view lets deferred GC collect its files
    del view, fresh
    log.run_gc()
    assert log.telemetry()["gc_deferred"] == 0


def test_gc_gated_by_stability_watermark(tmp_path):
    """Checkpoint advancement consumes ONLY watermark-cleared
    segments: with the mark mid-log the base stops there; clearing the
    mark lets the fold finish and the folded files disappear."""
    applied = list(applied_log_tree(mixed_ops(30))._log)
    log = tiered_copy(applied, tmp_path, "wm", hot_ops=8,
                      gc_min_segs=2, auto_stable=False)
    tele = log.telemetry()
    assert tele["segments"]["cold"] >= 4 and tele["base_ops"] == 0
    files_before = set(os.listdir(tmp_path / "wm"))
    # nothing stable yet → nothing folds
    log.run_gc()
    assert log.telemetry()["base_ops"] == 0
    # mid-log watermark → base advances AT MOST to the mark
    mark = len(log) // 2
    log.set_stable_mark(mark)
    log.run_gc()
    tele = log.telemetry()
    assert 0 < tele["base_ops"] <= mark
    assert tele["compactions"] == 1
    # full watermark → everything cold folds; old segment files GC'd
    log.set_stable_mark(len(log))
    log.run_gc()
    tele = log.telemetry()
    assert tele["base_ops"] == len(log) - tele["hot_ops"]
    assert tele["segments"]["cold"] == 0
    files_after = set(os.listdir(tmp_path / "wm"))
    assert not (files_before & files_after), \
        "folded segment files must be collected"
    # reads still logically identical after base advancement
    assert list(log) == applied


# -- truncate / restore ------------------------------------------------------


def test_truncate_into_cold_tier(tmp_path):
    applied = list(applied_log_tree(mixed_ops(20))._log)
    flat = OpLog(applied)
    log = tiered_copy(applied, tmp_path, "trunc", hot_ops=8)
    assert log.telemetry()["segments"]["cold"] >= 2
    cut = 11        # inside the cold range
    log.truncate(cut)
    flat.truncate(cut)
    assert len(log) == cut
    assert list(log) == applied[:cut]
    for op in applied[:cut]:
        if isinstance(op, Add):
            assert log.index_of_add(op.ts) == flat.index_of_add(op.ts)
    for op in applied[cut:]:
        if isinstance(op, Add) and flat.index_of_add(op.ts) is None:
            assert log.index_of_add(op.ts) is None
    # the log keeps working: append + re-spill + windows
    log.extend([Delete((ts(1, 1),))])
    assert list(log) == applied[:cut] + [Delete((ts(1, 1),))]
    log.maybe_spill()
    assert list(log) == applied[:cut] + [Delete((ts(1, 1),))]


def test_restore_checkpoint_plus_tail_bit_identical(tmp_path):
    """A tiered checkpoint restore must be fingerprint-equal —
    bit-identical merge state — to the full-replay tree: same log,
    same clocks, same visible sequence, same replica-independent
    state fingerprint, and a follow-up merge converges identically."""
    big = chain_ops(1, 1500)            # kernel-path bulk
    small = chain_ops(2, 30)            # host-path edits
    t = engine.init(0)
    t.enable_log_tiering(str(tmp_path / "ckpt"), hot_ops=256,
                         gc_min_segs=2)
    t.apply_packed(packed_mod.pack(big, max_depth=4))
    for op in small:
        t.apply(op)
    t.apply(Delete((ts(2, 30),)))
    assert t._log.spills >= 1
    t.checkpoint_tiered(str(tmp_path / "ckpt"))

    r = engine.TpuTree.restore_tiered(str(tmp_path / "ckpt"))
    replay = engine.init(0)
    replay.apply(op_mod.from_list(big + small + [Delete((ts(2, 30),))]))
    assert list(r._log) == list(t._log) == list(replay._log)
    assert r._replicas == t._replicas == replay._replicas
    assert r.visible_values() == replay.visible_values()
    snap_r = snapshot_mod.derive("d", 0, r)
    snap_t = snapshot_mod.derive("d", 7, t)
    snap_o = snapshot_mod.derive("d", 3, replay)
    assert snap_r.state_fingerprint() == snap_t.state_fingerprint() \
        == snap_o.state_fingerprint()
    # restored tree keeps merging bit-identically to the replay oracle
    more = chain_ops(3, 1100)
    r.apply_packed(packed_mod.pack(more, max_depth=4))
    replay.apply(op_mod.from_list(more))
    assert r.visible_values() == replay.visible_values()
    assert snapshot_mod.derive("d", 0, r).state_fingerprint() == \
        snapshot_mod.derive("d", 0, replay).state_fingerprint()


def test_missing_or_corrupt_segment_is_typed_checkpoint_error(tmp_path):
    t = engine.init(0)
    t.enable_log_tiering(str(tmp_path / "bad"), hot_ops=64)
    t.apply_packed(packed_mod.pack(chain_ops(1, 600), max_depth=4))
    assert t._log.spills >= 1
    t.checkpoint_tiered(str(tmp_path / "bad"))
    seg_files = [f for f in os.listdir(tmp_path / "bad")
                 if f.startswith("seg-")]
    assert seg_files
    victim = tmp_path / "bad" / seg_files[0]

    # corrupt: truncated bytes → typed error at restore (the light
    # open reads the file), never a silent partial log
    blob = victim.read_bytes()
    victim.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(CheckpointError):
        engine.TpuTree.restore_tiered(str(tmp_path / "bad"))

    # missing entirely → typed error too
    victim.unlink()
    with pytest.raises(CheckpointError):
        engine.TpuTree.restore_tiered(str(tmp_path / "bad"))

    # and a LIVE log whose spilled file vanishes behind its back
    # surfaces the same typed error when a cold read needs it
    live = OpLog(chain_ops(1, 60))
    live.enable_tiering(str(tmp_path / "live"), hot_ops=8,
                        cache_segments=1)
    live.maybe_spill()
    for f in os.listdir(tmp_path / "live"):
        os.remove(tmp_path / "live" / f)
    with pytest.raises(CheckpointError):
        live.materialize(0, 10)
    with pytest.raises(CheckpointError):
        live.view(4).window(ts(1, 1), 4)


def test_corrupt_manifest_is_typed(tmp_path):
    t = engine.init(0)
    t.enable_log_tiering(str(tmp_path / "m"), hot_ops=64)
    t.apply_packed(packed_mod.pack(chain_ops(1, 300), max_depth=4))
    t.checkpoint_tiered(str(tmp_path / "m"))
    (tmp_path / "m" / "manifest.json").write_text("{not json")
    with pytest.raises(CheckpointError):
        engine.TpuTree.restore_tiered(str(tmp_path / "m"))
    with pytest.raises(CheckpointError):
        engine.TpuTree.restore_tiered(str(tmp_path / "nowhere"))


# -- fingerprints across tier layouts ---------------------------------------


def test_state_fingerprint_layout_independent(tmp_path):
    """Converged replicas with DIFFERENT tier layouts (one untiered,
    one spilled, one spilled+compacted) must agree on the replica-
    independent fingerprint: it hashes the logical op extent, never
    the physical segmentation."""
    ops = chain_ops(1, 900) + chain_ops(2, 50)
    flat = engine.init(0)
    flat.apply_packed(packed_mod.pack(ops, max_depth=4))
    spilled = engine.init(0)
    spilled.enable_log_tiering(str(tmp_path / "s"), hot_ops=128,
                               gc_min_segs=99)      # spill, no fold
    spilled.apply_packed(packed_mod.pack(ops, max_depth=4))
    folded = engine.init(0)
    folded.enable_log_tiering(str(tmp_path / "f"), hot_ops=64,
                              gc_min_segs=2)        # spill AND fold
    folded.apply_packed(packed_mod.pack(ops[:700], max_depth=4))
    folded.apply_packed(packed_mod.pack(ops, max_depth=4))
    assert spilled._log.spills >= 1 and folded._log.compactions >= 1
    snaps = [snapshot_mod.derive("doc", i, t)
             for i, t in enumerate((flat, spilled, folded))]
    assert snaps[0].log_length == snaps[1].log_length \
        == snaps[2].log_length == len(ops)
    fps = {s.state_fingerprint() for s in snaps}
    assert len(fps) == 1, "tier layout leaked into the fingerprint"
    # while the physical layouts genuinely differ
    assert len({s.log_segments for s in snaps}) >= 2


# -- memory bound ------------------------------------------------------------


def test_memory_bound_500k_resident_is_o_hot_window(tmp_path):
    """The headline memory claim, tier-1-sized: a 500k-op document's
    resident log bytes after spill stay O(hot window) — ≤10% of the
    untiered log measured by the SAME estimator, with the hot tier at
    its budget and the rest on disk."""
    from crdt_graph_tpu.bench import workloads
    n = 500_000
    hot = 8192
    arrs = workloads.chain_workload(n_replicas=8, n_ops=n)
    p = packed_mod.PackedOps(
        kind=arrs["kind"], ts=arrs["ts"],
        parent_ts=arrs["parent_ts"], anchor_ts=arrs["anchor_ts"],
        depth=arrs["depth"], paths=arrs["paths"],
        value_ref=arrs["value_ref"], pos=arrs["pos"],
        values=[f"v{i}" for i in range(n)], num_ops=n,
        parent_pos=arrs["parent_pos"], anchor_pos=arrs["anchor_pos"],
        target_pos=arrs["target_pos"], ts_rank=arrs["ts_rank"],
        hints_vouched=True)

    untiered = OpLog()
    untiered.extend_packed(p)
    # the untiered serving shape builds the ts index on its first
    # since-pull — include that honestly on the untiered side
    untiered.view(1).since_bytes(int(arrs["ts"][n - 10]))
    untiered_bytes = untiered.resident_bytes()

    log = OpLog()
    log.extend_packed(p)
    # folding disabled: the cold tier stays segment-granular, so a
    # cold catch-up read loads ONE bounded segment, not the backlog
    log.enable_tiering(str(tmp_path / "mem"), hot_ops=hot,
                       gc_min_segs=10_000)
    log.maybe_spill()
    tele = log.telemetry()
    # spill hysteresis keeps at most hot + hot/4 resident
    assert tele["hot_ops"] <= hot + hot // 4
    assert tele["cold_ops"] + tele["base_ops"] == n - tele["hot_ops"]
    resident = tele["resident_bytes"]
    assert resident <= 0.10 * untiered_bytes, \
        (resident, untiered_bytes)
    # and the log still answers: a steady-state window off the hot
    # tail touches no cold segment
    loads0 = tele["segment_loads"]
    view = log.view(1)
    body, meta = view.window(int(arrs["ts"][n - 100]), 64)
    assert meta["found"] and meta["count"] >= 1
    assert log.telemetry()["segment_loads"] == loads0
    # a cold window loads exactly what it serves (bounded by the LRU)
    body, meta = view.window(int(arrs["ts"][100]), 64)
    assert meta["found"] and meta["more"]
    assert log.telemetry()["segment_loads"] >= loads0 + 1
    assert log.telemetry()["cache_bytes"] <= 0.15 * untiered_bytes


# -- chunked checkpoint base (ISSUE 11) --------------------------------------


def test_base_chunks_window_identity_at_chunk_seams(tmp_path):
    """With the base split into bounded chunks, every window —
    including ones whose terminator sits exactly on a chunk seam or
    whose body spans several chunks — stays byte- and meta-identical
    to the untiered ``packed_since_window``."""
    ops = mixed_ops(30)
    t = applied_log_tree(ops)
    applied = list(t._log)
    p = packed_mod.pack(applied, max_depth=4)
    log = tiered_copy(applied, tmp_path, "bc", hot_ops=8,
                      gc_min_segs=1, base_chunk_ops=16)
    log.set_stable_mark(len(log))
    log.run_gc()
    tele = log.telemetry()
    assert tele["segments"]["base"] >= 3, tele
    assert tele["base_ops"] == len(log) - tele["hot_ops"]
    view = log.view(max_depth=4)
    adds = [op.ts for op in applied if isinstance(op, Add)]
    for since in [0] + adds + adds[-2:]:
        for limit in (0, 1, 3, 8, 16, 17, 1000):
            want = engine.packed_since_window(p, since, limit)
            got = view.window(since, limit)
            assert got[0] == want[0], (since, limit)
            assert got[1] == want[1], (since, limit)
    # incremental folds keep appending chunks, identically
    log.extend(chain_ops(7, 40))
    log.maybe_spill()
    log.set_stable_mark(len(log))
    log.run_gc()
    assert log.telemetry()["segments"]["base"] >= \
        tele["segments"]["base"]
    full = log.view(max_depth=4)
    p2 = full.to_packed()
    for since in (0, adds[5], adds[-1]):
        want = engine.packed_since_window(p2, since, 9)
        got = log.view(max_depth=4).window(since, 9)
        assert got[0] == want[0] and got[1] == want[1], since


def test_mid_history_window_opens_only_covering_chunks(tmp_path):
    """The resident-bytes bound (acceptance): a mid-history catch-up
    window over a fully-folded log loads ONLY its covering base
    chunks — the cache holds O(covering chunks), never the whole
    base, and the byte-denominated LRU (GRAFT_OPLOG_CACHE_MB) counts
    its evictions."""
    from crdt_graph_tpu.bench import workloads
    from crdt_graph_tpu.oplog import OpLog as _OpLog, _packed_resident
    n = 60_000
    arrs = workloads.chain_workload(n_replicas=8, n_ops=n)
    p = packed_mod.PackedOps(
        kind=arrs["kind"], ts=arrs["ts"],
        parent_ts=arrs["parent_ts"], anchor_ts=arrs["anchor_ts"],
        depth=arrs["depth"], paths=arrs["paths"],
        value_ref=arrs["value_ref"], pos=arrs["pos"],
        values=[f"v{i}" for i in range(n)], num_ops=n,
        parent_pos=arrs["parent_pos"], anchor_pos=arrs["anchor_pos"],
        target_pos=arrs["target_pos"], ts_rank=arrs["ts_rank"],
        hints_vouched=True)
    chunk = 8192
    log = _OpLog()
    log.extend_packed(p)
    log.enable_tiering(str(tmp_path / "cw"), hot_ops=2048,
                       gc_min_segs=1, base_chunk_ops=chunk)
    log.maybe_spill()
    log.set_stable_mark(len(log))
    log.run_gc()
    tele = log.telemetry()
    assert tele["segments"]["base"] >= 6, tele
    whole_base_resident = _packed_resident(p)  # upper-ruler: full log
    view = log.view(1)
    loads0 = tele["segment_loads"]
    # one bounded mid-history window → at most the 1-2 chunks that
    # cover it load; the cache stays O(chunk), not O(base)
    body, meta = view.window(int(arrs["ts"][n // 2]), 256)
    assert meta["found"] and meta["count"] >= 256
    tele = log.telemetry()
    assert 1 <= tele["segment_loads"] - loads0 <= 2, tele
    per_chunk = whole_base_resident * (chunk / n)
    assert tele["cache_bytes"] <= 2.5 * per_chunk, \
        (tele["cache_bytes"], per_chunk, whole_base_resident)
    assert tele["cache_bytes"] < 0.2 * whole_base_resident
    # a sweep across the whole history stays byte-bounded by the LRU
    # knob and counts evictions (the shared-sizing satellite)
    small = _OpLog()
    small.extend_packed(p)
    small.enable_tiering(str(tmp_path / "cw2"), hot_ops=2048,
                         gc_min_segs=1, base_chunk_ops=chunk,
                         cache_mb=1)
    small.maybe_spill()
    small.set_stable_mark(len(small))
    small.run_gc()
    sview = small.view(1)
    for i in range(4, n, n // 9):
        body, meta = sview.window(int(arrs["ts"][i]), 128)
        assert meta["found"], i
    stele = small.telemetry()
    assert stele["cache_evictions"] >= 1, stele
    assert stele["cache_bytes"] <= 2 * (1 << 20), stele


def test_fold_rewrites_only_trailing_partial_chunk(tmp_path):
    """Write-amplification bound: an incremental fold may rewrite the
    trailing PARTIAL chunk but never a full one — earlier full chunks
    keep their exact files across later folds."""
    applied = list(applied_log_tree(mixed_ops(40))._log)
    log = tiered_copy(applied, tmp_path, "wa", hot_ops=8,
                      gc_min_segs=1, base_chunk_ops=16)
    log.set_stable_mark(len(log))
    log.run_gc()
    full_before = {cs.path for cs in log._bases
                   if cs.length == 16}
    assert full_before
    log.extend(chain_ops(8, 60))
    log.maybe_spill()
    log.set_stable_mark(len(log))
    log.run_gc()
    after = {cs.path for cs in log._bases}
    assert full_before <= after, \
        "a fold rewrote full base chunks (unbounded write amp)"
    assert list(log) == applied + chain_ops(8, 60)


# -- direct fold: a stable spill sealed straight into base chunks -----------


def _interleaved_chains(n_per):
    a, b = chain_ops(1, n_per), chain_ops(2, n_per)
    return [op for pair in zip(a, b) for op in pair]


def _tier_files(dirpath, prefix):
    return sorted(f for f in os.listdir(dirpath) if f.startswith(prefix))


@pytest.mark.parametrize("hot_ops,chunk,n0,shape", [
    pytest.param(16, 16, 0, "deletes", id="chunk-eq-segment"),
    pytest.param(8, 24, 36, "deletes", id="trailing-partial-merged"),
    pytest.param(8, 5, 20, "chains", id="chunk-lt-segment"),
    pytest.param(16, 1000, 12, "deletes", id="one-chunk"),
    pytest.param(512, 1024, 0, "chunked", id="chunked-apply-parts"),
    pytest.param(512, 1024, 0, "one-batch", id="one-batch-row-range"),
])
def test_direct_fold_matches_spill_then_fold(tmp_path, hot_ops, chunk,
                                             n0, shape):
    """A stable spill sealed straight into base chunks leaves what the
    segment path's spill-then-fold leaves: the same chunk files and
    boundaries, the same columns, and reads — object iteration,
    windows at every seam, the state fingerprint — identical to the
    untiered ruler.  The reference side is forced onto the segment
    path by raising ``gc_min_segs`` for the spill, then folded.  In
    ``chunked`` several ``apply_packed`` calls in one batch leave the
    hot tail in parts of the chunk size, which the direct fold seals
    as they are; in ``one-batch`` one ``apply_packed`` leaves one
    batch, whose spilled row range the direct fold cuts its chunks
    from."""
    ops = {"deletes": lambda: mixed_ops(30),
           "chains": lambda: _interleaved_chains(36),
           "chunked": lambda: _interleaved_chains(1100),
           "one-batch": lambda: _interleaved_chains(1100)}[shape]()
    plain = applied_log_tree(ops)
    applied = list(plain._log)
    ruler = plain.log_view().to_packed()

    partial, folds0 = {}, {}

    def build(name, direct):
        t = engine.init(0)
        t.enable_log_tiering(str(tmp_path / name), hot_ops=hot_ops,
                             gc_min_segs=2, base_chunk_ops=chunk)
        if n0:
            # identical on both sides: may leave a partial base chunk
            t.apply(op_mod.from_list(applied[:n0]))
        partial[name] = [cs.path for cs in t._log._bases
                         if cs.length < chunk]
        folds0[name] = t._log.direct_folds
        cfg = t._log._cfg
        if not direct:
            cfg.gc_min_segs = 10**9
        if shape == "chunked":
            # one batch: the spill waits for the last part's commit
            p = packed_mod.pack(applied[n0:])
            parts = [packed_mod.select_rows(
                p, np.arange(a, min(a + chunk, p.num_ops)))
                for a in range(0, p.num_ops, chunk)]
            t.batch([lambda t, q=q: t.apply_packed(q) for q in parts])
        elif shape == "one-batch":
            t.apply_packed(packed_mod.pack(applied[n0:]))
        else:
            t.apply(op_mod.from_list(applied[n0:]))
        if not direct:
            cfg.gc_min_segs = 2
            t._log.run_gc()
        return t

    d, s = build("direct", True), build("segs", False)
    dl, sl = d._log, s._log
    assert dl.direct_folds - folds0["direct"] == 1
    assert sl.direct_folds - folds0["segs"] == 0
    assert sl.segments_gc >= 2
    if n0 == 36:
        # the first commit left a partial chunk the direct fold merged
        # with the new rows and rewrote
        assert partial["direct"]
        assert not set(partial["direct"]) & {cs.path for cs in dl._bases}
    assert not _tier_files(tmp_path / "direct", "seg-")
    assert not dl._cold and not sl._cold
    # the same chunks: names (start, length, generation), columns
    assert [(os.path.basename(cs.path), cs.start, cs.length)
            for cs in dl._bases] == \
        [(os.path.basename(cs.path), cs.start, cs.length)
         for cs in sl._bases]
    for a, b in zip(dl._bases, sl._bases):
        pa, pb = a.load(), b.load()
        for col in ("kind", "ts", "parent_ts", "anchor_ts", "depth",
                    "paths", "value_ref", "parent_pos", "anchor_pos",
                    "target_pos", "ts_rank"):
            assert np.array_equal(getattr(pa, col)[:a.length],
                                  getattr(pb, col)[:b.length]), col
        assert pa.values == pb.values
        assert pa.hints_vouched == pb.hints_vouched
        assert np.array_equal(a.add_ts, b.add_ts)
        assert np.array_equal(a.add_pos, b.add_pos)
    assert dl.telemetry()["base_ops"] == sl.telemetry()["base_ops"] > 0
    # reads: objects, windows and suffixes at every seam
    assert list(dl) == list(sl) == applied
    views = (d.log_view(), s.log_view())
    adds = [op.ts for op in applied if isinstance(op, Add)]
    sinces = [0] + adds + adds[-2:]
    if shape == "chunked":
        # each chunk seam, either side of it, and a sample between
        sinces = [0] + adds[::97] + [
            adds[i] for c in range(chunk, len(adds), chunk)
            for i in (c - 2, c - 1, c)] + adds[-2:]
    for since in sinces:
        for limit in (1, 3, 5, 8, 16, 17, 1000):
            want = engine.packed_since_window(ruler, since, limit)
            for v in views:
                assert v.window(since, limit) == want, (since, limit)
        want = engine.packed_since_bytes(ruler, since)
        for v in views:
            assert v.since_bytes(since) == want, since
    fps = {snapshot_mod.derive("d", 0, t).state_fingerprint()
           for t in (d, s, plain)}
    assert len(fps) == 1


def _engagement_log(tmp_path, case):
    """A log with an 8-op hot budget and ``gc_min_segs`` 4, driven into
    the spill shape ``case`` names (the engagement test below)."""
    log = OpLog()
    log.enable_tiering(str(tmp_path / case), hot_ops=8, gc_min_segs=4,
                       auto_stable=case != "watermark-below",
                       base_chunk_ops=16)
    if case == "cold-not-empty":
        log.extend(chain_ops(1, 32))
        log.maybe_spill()                   # 3 segments: no fold yet
        assert log.telemetry()["segments"]["cold"] == 3
        log.extend(chain_ops(1, 48, start=33))
        log.maybe_spill()
        return log
    n = {"catchup": 48, "few-segments": 32}.get(case, 48)
    log.extend_packed(packed_mod.pack(chain_ops(1, n), max_depth=4))
    if case == "watermark-below":
        log.set_stable_mark(8)
    if case == "spill-all":
        log.spill_all()
    else:
        log.maybe_spill()
    return log


@pytest.mark.parametrize("case,want", [
    # (direct_folds, compactions, segments_gc, cold segments, base ops)
    ("catchup", (1, 1, 0, 0, 40)),
    ("few-segments", (0, 0, 0, 3, 0)),
    ("cold-not-empty", (0, 1, 9, 0, 72)),
    ("watermark-below", (0, 0, 0, 5, 0)),
    ("spill-all", (0, 0, 0, 6, 0)),
])
def test_direct_fold_engages_only_when_the_fold_would_take_every_segment(
        tmp_path, case, want):
    """A catch-up-shaped spill (at least ``gc_min_segs`` segments of
    rows, all stable, cold tier empty) seals straight into the base:
    one compaction, no segment collected.  Every other shape keeps the
    cold segments it sealed before: a spill too small to fold, a cold
    tier that must fold first, a watermark below the range, and
    ``spill_all`` (whose caller runs no fold)."""
    log = _engagement_log(tmp_path, case)
    tele = log.telemetry()
    got = (tele["direct_folds"], tele["compactions"],
           tele["segments_gc"], tele["segments"]["cold"],
           tele["base_ops"])
    assert got == want, tele
    # the sealed cold segments are on disk (a fold deletes them)
    assert len(_tier_files(tmp_path / case, "seg-")) == want[3]
    if case == "catchup":
        assert tele["spills"] == len(log._bases) == 3
    assert list(log) == chain_ops(1, len(log))


# -- serving integration + exposition ----------------------------------------


def test_serving_engine_tiers_by_default_and_prom_round_trips():
    """A served document spills under sustained writes with the
    default-on cascade, keeps serving byte-correct windows, exports
    the ``crdt_oplog_*`` families under the strict naming contract,
    and reports tier state in /metrics."""
    from crdt_graph_tpu.serve import ServingEngine
    eng = ServingEngine(oplog_hot_ops=512)
    try:
        doc_id = "casc"
        for k in range(4):
            ops = chain_ops(1, 600, start=1 + 600 * k)
            eng.get(doc_id).apply_body(
                json_codec.dumps(Batch(tuple(ops))))
        assert eng.flush(timeout=60)
        doc = eng.get(doc_id, create=False)
        tele = doc.tree._log.telemetry()
        assert tele["tiered"] and tele["spills"] >= 1
        assert tele["hot_ops"] < 2400
        # windows off the published snapshot match the untiered ruler
        p = packed_mod.pack(chain_ops(1, 2400), max_depth=16)
        for since in (0, ts(1, 1), ts(1, 600), ts(1, 2399)):
            want = engine.packed_since_window(p, since, 100)
            got = doc.ops_since_window(since, 100)
            assert got[0] == want[0], since
            # the served window adds the body validator (ISSUE 16) on
            # top of the ruler's meta
            got_meta = {k: v for k, v in got[1].items() if k != "etag"}
            assert got_meta == want[1], since
        # /metrics carries the tier state
        assert doc.metrics()["oplog"]["spills"] >= 1
        # strict prom round trip with the new families present
        fams = prom_mod.parse_text(eng.render_prom())
        for fam in ("crdt_oplog_spills_total",
                    "crdt_oplog_compactions_total",
                    "crdt_oplog_segments_gc_total",
                    "crdt_oplog_direct_folds_total",
                    "crdt_oplog_segment_loads_total",
                    "crdt_oplog_resident_bytes",
                    "crdt_oplog_stable_mark",
                    "crdt_oplog_tier_ops", "crdt_oplog_tier_bytes",
                    "crdt_oplog_segment_load_ms"):
            assert fam in fams, fam
        tiers = {lbl["tier"] for _, lbl, _ in
                 fams["crdt_oplog_tier_ops"]["samples"]}
        assert tiers == {"hot", "cold", "base"}
        spills = [v for _, lbl, v in
                  fams["crdt_oplog_spills_total"]["samples"]
                  if lbl["doc"] == doc_id]
        assert spills and spills[0] >= 1
        folds = [v for _, lbl, v in
                 fams["crdt_oplog_direct_folds_total"]["samples"]
                 if lbl["doc"] == doc_id]
        assert folds == [tele["direct_folds"]]
        # the spill scratch tier dies with the engine
        spill_dir = eng.oplog_dir
        assert os.path.isdir(spill_dir)
    finally:
        eng.close()
    assert not os.path.exists(spill_dir)


def test_snapshot_pins_view_across_spill_and_bootstrap_roundtrip():
    """A published snapshot keeps serving its exact generation while
    the live log spills underneath it, and its /snapshot bootstrap
    bytes restore to the same state."""
    from crdt_graph_tpu.serve import ServingEngine
    eng = ServingEngine(oplog_hot_ops=256)
    try:
        doc = eng.get("pin")
        doc.apply_body(json_codec.dumps(Batch(tuple(chain_ops(1, 400)))))
        assert eng.flush(timeout=60)
        snap = doc.snapshot_view()
        want_bytes = snap.ops_since_bytes(0)
        # push more → spill moves the first batch to disk
        doc.apply_body(json_codec.dumps(Batch(tuple(chain_ops(2, 700)))))
        assert eng.flush(timeout=60)
        assert doc.tree._log.spills >= 1
        # the OLD snapshot still serves its own generation, unchanged
        assert snap.log_length == 400
        assert snap.ops_since_bytes(0) == want_bytes
        # the NEW snapshot's binary bootstrap restores bit-identically
        new = doc.snapshot_view()
        assert new.log_length == 1100
        r = engine.TpuTree.restore_packed(
            io.BytesIO(new.checkpoint_bytes()), replica=7)
        assert r.log_length == 1100
        assert snapshot_mod.derive("pin", 0, r).state_fingerprint() \
            == new.state_fingerprint()
    finally:
        eng.close()


def test_checkpoint_tiered_to_foreign_dir_survives_engine(tmp_path):
    """A served document tiers into EPHEMERAL engine scratch;
    ``checkpoint_tiered(dir)`` must honor the requested dir (copying
    the segment files) so the checkpoint survives the engine that
    wrote it — checkpointing into the scratch dir would be silently
    destroyed by ``engine.close()``."""
    from crdt_graph_tpu.serve import ServingEngine
    eng = ServingEngine(oplog_hot_ops=256)
    target = str(tmp_path / "backup")
    try:
        doc = eng.get("ckpt")
        doc.apply_body(json_codec.dumps(Batch(tuple(chain_ops(1, 900)))))
        assert eng.flush(timeout=60)
        assert doc.tree._log.spills >= 1
        want_fp = doc.snapshot_view().state_fingerprint()
        path = doc.tree.checkpoint_tiered(target)
        assert path.startswith(target)
    finally:
        eng.close()
    # the scratch tier is gone with the engine; the checkpoint is not
    r = engine.TpuTree.restore_tiered(target)
    assert r.log_length == 900
    assert snapshot_mod.derive("ckpt", 0, r).state_fingerprint() \
        == want_fp


def test_hot_bytes_budget_spills_by_bytes(tmp_path):
    """GRAFT_OPLOG_HOT_BYTES semantics: with large per-op values the
    BYTE budget triggers the spill long before the op budget would,
    and its hysteresis is byte-denominated."""
    ops = [Add(ts(1, c), ((ts(1, c - 1) if c > 1 else 0),), "x" * 2000)
           for c in range(1, 201)]
    log = OpLog()
    log.extend_packed(packed_mod.pack(ops, max_depth=4))
    budget = 100_000
    log.enable_tiering(str(tmp_path / "hb"), hot_ops=100_000,
                       hot_bytes=budget, gc_min_segs=99)
    assert log.maybe_spill()
    tele = log.telemetry()
    assert tele["spills"] >= 1
    assert tele["hot_ops"] < 200
    assert tele["hot_bytes"] <= 2 * budget, tele


# -- headline artifact (slow wrapper) ----------------------------------------


@pytest.mark.slow
def test_bench_oplog_headline_full(tmp_path):
    """The committed-artifact run (BENCH_OPLOG_r01_cpu.json shape):
    1M-op config-5 document, default cascade knobs — resident log
    bytes ≤10% of untiered, checkpoint+tail restore ≥5× faster than
    full replay, bit-identical merge fingerprints throughout."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_oplog_headline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_oplog_headline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(out_path=str(tmp_path / "BENCH_OPLOG_test.json"))
    assert out["fingerprints_equal"]
    assert out["resident"]["ratio"] <= 0.10, out["resident"]
    assert out["restore"]["speedup_serving_ready"] >= 5.0, \
        out["restore"]
    assert out["tiers"]["spills"] >= 1
    assert out["windows"]["hot_p50_ms"] is not None


# -- deterministic fleet round: GC mid-sync ---------------------------------


def _req(port, method, path, body=None, headers=None, timeout=60):
    conn = HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw, dict(resp.getheaders())
    finally:
        conn.close()


def test_fleet_gc_mid_sync_converges_with_oracle(monkeypatch):
    """Tier-1 fleet determinism: a 2-node fleet with tiny hot budgets
    syncs a spilled document in bounded window chains; checkpoint
    advancement + segment GC run MID-CHAIN (watermark at the puller's
    half-way mark), the chain resumes across the fold, and the session
    oracle reports fingerprint-equal convergence with 0 violations."""
    from crdt_graph_tpu.cluster import FleetServer, MemoryKV
    from crdt_graph_tpu.obs.oracle import SessionOracle
    monkeypatch.setenv("GRAFT_OPLOG_HOT_OPS", "96")
    monkeypatch.setenv("GRAFT_OPLOG_GC_SEGS", "2")
    kv = MemoryKV()
    fleet = {}
    for n in ("n0", "n1"):
        fleet[n] = FleetServer(n, kv, ttl_s=600.0,
                               ae_interval_s=3600.0, delta_cap=300)
    try:
        for fs in fleet.values():
            fs.node.refresh_ring()
        ring = fleet["n0"].node.ring()
        doc = next(f"doc{i}" for i in range(500)
                   if ring.primary(f"doc{i}") == "n0")
        # 1200 ops through the primary → several cold segments
        ops = chain_ops(3, 1200)
        st, raw, _ = _req(fleet["n0"].port, "POST", f"/docs/{doc}/ops",
                          body=json_codec.dumps(Batch(tuple(ops))))
        assert st == 200 and json.loads(raw)["accepted"]
        assert fleet["n0"].node.engine.flush(timeout=60)
        log0 = fleet["n0"].node.engine.get(doc).tree._log
        assert log0.spills >= 1
        segs_before = log0.telemetry()["segments"]["cold"]
        assert segs_before >= 2
        # fleet logs must NOT auto-stabilize: no peer pulled yet
        log0.run_gc()
        assert log0.telemetry()["base_ops"] == 0

        # n1 pulls a PARTIAL chain (2 bounded windows), then stops —
        # mid-sync by construction
        ae1 = fleet["n1"].node.antientropy
        ae1.max_windows_per_doc = 2
        ae1.sync_now()              # partial: chain cut after 2 windows
        marks = fleet["n0"].node._peer_marks.get(doc, {})
        assert "n1" in marks and marks["n1"] > 0
        # the primary folds what n1 provably consumed — and ONLY that
        fleet["n0"].node.update_stability()
        tele = log0.telemetry()
        mark_pos = log0.index_of_add(marks["n1"])
        assert tele["stable_mark"] == mark_pos
        assert tele["base_ops"] <= mark_pos
        gc_ran = tele["compactions"] >= 1
        assert gc_ran, "GC must advance the base mid-sync"
        # unauthenticated X-Ae-Peer values must not accumulate: marks
        # from non-members are pruned on every stability round
        fleet["n0"].node.note_peer_mark(doc, "not-a-member", 12345)
        fleet["n0"].node.update_stability()
        assert "not-a-member" not in \
            fleet["n0"].node._peer_marks.get(doc, {})

        # the chain RESUMES across the fold and completes
        ae1.max_windows_per_doc = 10_000
        assert ae1.sync_now() == {"n0": True}
        assert fleet["n1"].node.engine.flush(timeout=60)
        fleet["n0"].node.update_stability()

        # oracle-verified fingerprint-equal convergence
        oracle = SessionOracle()
        fps = {}
        for name, fs in fleet.items():
            st, raw, hdr = _req(fs.port, "GET", f"/docs/{doc}")
            assert st == 200
            fps[name] = hdr["X-State-Fingerprint"]
            oracle.observe_replica_state(
                doc, f"{name}.1", hdr["X-State-Fingerprint"])
        assert fps["n0"] == fps["n1"], fps
        violations = oracle.finalize()
        assert violations == [], violations
        assert oracle.stats()["violations_total"] == 0
    finally:
        for fs in fleet.values():
            try:
                fs.stop()
            except Exception:   # noqa: BLE001 — teardown boundary
                pass
