"""Crash-durable acked writes (ISSUE 9): the group-commit WAL under
the cascade op-log, recovery-to-serving, and the crash-point matrix.

The contract under test: an acknowledged write survives a kill at ANY
point — the WAL fsyncs before the ack is released, spill/fold
manifests keep the tiers reopenable, recovery replays the WAL tail
through the ordinary apply path, and the recovered log's windows stay
byte-identical to the untiered ``packed_since_window`` contract.
Corruption is typed: a torn final record is tolerated and counted, a
mid-log checksum flip raises ``WalError``, and a full disk sheds
writes as honest 503s while the server keeps serving.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu import engine
from crdt_graph_tpu import wal as wal_mod
from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.codec import packed as packed_mod
from crdt_graph_tpu.core.operation import Add, Batch, Delete
from crdt_graph_tpu.obs import flight as flight_mod
from crdt_graph_tpu.obs import oracle as oracle_mod
from crdt_graph_tpu.obs import prom as prom_mod
from crdt_graph_tpu.serve import (SchedulerStopped, ServingEngine,
                                  WalUnavailable)

OFF = 2**32


def ts(r, c):
    return r * OFF + c


def chain_ops(r, n, start=1):
    out = []
    prev = ts(r, start - 1) if start > 1 else 0
    for c in range(start, start + n):
        out.append(Add(ts(r, c), (prev,), f"v{r}.{c}"))
        prev = ts(r, c)
    return out


def _submit(eng, doc, ops):
    return eng.submit(doc, json_codec.dumps(Batch(tuple(ops))))


def _windows_match_untiered(tree, sinces=(0,), limits=(0, 7)):
    """The recovered log's window answers vs engine.packed_since_window
    over its own full packing — the tiered/untiered byte contract."""
    view = tree.log_view()
    full = view.to_packed()
    for since in sinces:
        for limit in limits:
            if limit:
                b1, m1 = view.window(since, limit)
                b2, m2 = engine.packed_since_window(full, since, limit)
                assert b1 == b2 and m1 == m2, (since, limit)
            else:
                assert view.since_bytes(since) == \
                    engine.packed_since_bytes(full, since), since


# -- raw WAL format + corruption taxonomy ---------------------------------


def _raw_wal(tmp_path, n_records=3):
    w = wal_mod.Wal(str(tmp_path / "wal.log"))
    pos = 0
    for k in range(n_records):
        ops = chain_ops(1, 5, start=1 + 5 * k)
        pos += 5
        w.append(packed_mod.pack(ops), pos)
    w.sync()
    w.close()
    return w


def test_wal_scan_roundtrip_and_truncate_below(tmp_path):
    w = _raw_wal(tmp_path, n_records=3)
    records, torn, good = wal_mod.scan(w.path)
    assert [r[1] for r in records] == [5, 10, 15] and torn == 0
    # payloads decode back to the exact ops appended
    _, p = wal_mod._decode_payload(records[1][2])
    assert packed_mod.unpack_rows(p, 0, p.num_ops) == \
        chain_ops(1, 5, start=6)
    # truncation drops fully-covered records, keeps straddlers
    w2 = wal_mod.Wal(w.path)
    assert w2.truncate_below(10) == 2
    records, torn, _ = wal_mod.scan(w.path)
    assert [r[1] for r in records] == [15] and torn == 0
    # idempotent; nothing below the watermark left
    assert w2.truncate_below(10) == 0
    w2.close()


def test_wal_torn_final_record_tolerated_and_counted(tmp_path):
    w = _raw_wal(tmp_path, n_records=2)
    data = open(w.path, "rb").read()
    for cut in (7, 1, len(data) - wal_mod.scan(w.path)[0][1][0] - 3):
        torn_path = str(tmp_path / f"torn{cut}.log")
        with open(torn_path, "wb") as f:
            f.write(data[:-cut])
        records, torn, good = wal_mod.scan(torn_path)
        assert torn == 1 and len(records) == 1, cut
    # a crc flip on the FINAL record is a torn tail too (partial
    # payload write), not mid-log corruption
    flipped = bytearray(data)
    flipped[-3] ^= 0xFF
    flip_path = str(tmp_path / "flip-last.log")
    with open(flip_path, "wb") as f:
        f.write(bytes(flipped))
    records, torn, _ = wal_mod.scan(flip_path)
    assert torn == 1 and len(records) == 1


def test_wal_midlog_corruption_raises_typed(tmp_path):
    w = _raw_wal(tmp_path, n_records=3)
    data = bytearray(open(w.path, "rb").read())
    records, _, _ = wal_mod.scan(w.path)
    # flip a byte INSIDE the first record's payload: valid records
    # continue past it, so this must be WalError, never a partial scan
    data[records[0][0] + 12] ^= 0xFF
    with open(w.path, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(wal_mod.WalError):
        wal_mod.scan(w.path)
    # and recovery through replay_into refuses too
    t = engine.init(0)
    with pytest.raises(wal_mod.WalError):
        wal_mod.Wal(w.path).replay_into(t)
    # bad magic is typed as well
    with open(w.path, "wb") as f:
        f.write(b"NOTAWAL!" + bytes(16))
    with pytest.raises(wal_mod.WalError):
        wal_mod.scan(w.path)


def test_wal_duplicate_replay_idempotent_after_crash_mid_truncate(
        tmp_path):
    """A crash between the spill's manifest write and the WAL truncate
    leaves records the tiers already cover — replay must absorb them
    through apply dedup, bit-identically."""
    w = _raw_wal(tmp_path, n_records=3)
    ref = engine.init(0)
    ref.apply(Batch(tuple(chain_ops(1, 15))))

    # replay everything into a fresh tree, then replay the SAME file
    # again (the mid-truncate shape: every record is a duplicate)
    t = engine.init(0)
    stats = wal_mod.Wal(w.path).replay_into(t)
    assert stats["ops"] == 15 and t.log_length == 15
    again = wal_mod.Wal(w.path).replay_into(t)
    assert again["applied"] == 0, "duplicate replay must absorb"
    assert t.log_length == 15
    assert t.visible_values() == ref.visible_values()
    _windows_match_untiered(t, sinces=(0, ts(1, 1), ts(1, 9)))


def test_wal_records_deletes_and_replays_them(tmp_path):
    """Deletes ride WAL records like adds (the all-delete tail is the
    PR-6 window bug class — it must survive a crash too)."""
    ops = chain_ops(1, 8) + [Delete((ts(1, c),)) for c in (2, 5, 8)]
    ref = engine.init(0)
    ref.apply(Batch(tuple(ops)))
    w = wal_mod.Wal(str(tmp_path / "wal.log"))
    w.append(packed_mod.pack(ops), len(ops))
    w.sync()
    w.close()
    t = engine.init(0)
    wal_mod.Wal(w.path).replay_into(t)
    assert t.visible_values() == ref.visible_values()
    assert t.log_length == ref.log_length
    _windows_match_untiered(t, sinces=(0, ts(1, 3)), limits=(0, 4))


def test_wal_failed_append_repairs_to_record_boundary(tmp_path):
    """A failed append can leave partial bytes on disk (large records
    flush incrementally before the OSError).  The repair truncates
    back to the last good record boundary, so a LATER successful
    append never buries the garbage mid-log — a torn tail must stay a
    torn tail, never become fatal mid-log corruption at recovery."""
    w = _raw_wal(tmp_path, n_records=2)
    good = os.path.getsize(w.path)
    # the partially-flushed failed append's residue
    with open(w.path, "ab") as f:
        f.write(b"\x99" * 11)
    w2 = wal_mod.Wal(w.path)
    w2._size = good                  # what Wal tracked pre-failure
    w2._repair_locked(good)
    assert w2.repairs == 1
    w2.append(packed_mod.pack(chain_ops(1, 3, start=11)), 13)
    w2.sync()
    w2.close()
    records, torn, _ = wal_mod.scan(w.path)
    assert torn == 0 and len(records) == 3
    # and the appended record decodes
    _, p = wal_mod._decode_payload(records[-1][2])
    assert packed_mod.unpack_rows(p, 0, p.num_ops) == \
        chain_ops(1, 3, start=11)


# -- the serving path: durability, group commit, shedding -----------------


def _durable_engine(ddir, wal_sync="batch", **kw):
    kw.setdefault("oplog_hot_ops", 8)
    kw.setdefault("flight", flight_mod.FlightRecorder())
    return ServingEngine(durable_dir=str(ddir), wal_sync=wal_sync, **kw)


def test_recovery_to_serving_windows_epoch_and_metrics(tmp_path):
    eng = _durable_engine(tmp_path / "dur")
    ops = chain_ops(1, 30)
    for i in range(0, 30, 5):
        ok, _ = _submit(eng, "docA", ops[i:i + 5])
        assert ok
    doc = eng.get("docA")
    vals = doc.snapshot()
    m = doc.metrics()
    assert m["durable"] and m["epoch"] == 1 and m["wal"]["fsyncs"] >= 1
    assert eng.flush(20)
    # abandon WITHOUT close: everything written is on disk/page cache,
    # exactly what a kill leaves behind
    eng2 = _durable_engine(tmp_path / "dur")
    doc2 = eng2.get("docA", create=False)
    assert doc2 is not None, "recovery scan must reopen the doc"
    assert doc2.recovered and doc2.epoch == 2
    assert doc2.snapshot() == vals
    # recovered hot tail came through the WAL, tiers through the
    # manifest; windows stay byte-identical to untiered at the seams
    _windows_match_untiered(doc2.tree,
                            sinces=(0, ts(1, 1), ts(1, 17), ts(1, 28)),
                            limits=(0, 6))
    # steady-state WAL stayed O(hot tail): spills truncated it
    assert doc2.wal.telemetry()["size_bytes"] < 16384
    eng2.close()
    eng.close()


def test_group_commit_one_fsync_covers_coalesced_tickets(tmp_path):
    """batch mode: N tickets fused into one commit share ONE WAL
    record and ONE fsync — the group-commit amortization."""
    eng = _durable_engine(tmp_path / "dur", oplog_hot_ops=4096)
    eng.scheduler.pause()
    n = 6
    results = []

    def writer(rid):
        ops = [Add(ts(rid, 1), (0,), f"w{rid}")]
        results.append(_submit(eng, "gdoc", ops))

    threads = [threading.Thread(target=writer, args=(rid,),
                                daemon=True) for rid in range(2, 2 + n)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        d = eng.get("gdoc", create=False)
        if d is not None and len(d.queue) == n:
            break
        time.sleep(0.005)
    eng.scheduler.resume()
    for t in threads:
        t.join(30)
    assert len(results) == n and all(ok for ok, _ in results)
    doc = eng.get("gdoc")
    w = doc.wal.telemetry()
    assert w["appends"] == 1, w
    assert w["fsyncs"] == 1, w
    # the fsync is billed into the commit's flight stages
    rec = [r for r in eng.flight.records()
           if r.doc_id == "gdoc" and r.outcome == "committed"][-1]
    assert rec.coalesce_width == n
    assert "wal_fsync" in rec.stages_ms and "wal_append" in rec.stages_ms
    eng.close()


def test_commit_mode_fsyncs_every_commit(tmp_path):
    eng = _durable_engine(tmp_path / "dur", wal_sync="commit")
    for i in range(3):
        ok, _ = _submit(eng, "cdoc", chain_ops(1, 4, start=1 + 4 * i))
        assert ok
    w = eng.get("cdoc").wal.telemetry()
    assert w["fsyncs"] >= 3 and w["appends"] == 3
    eng.close()


def test_disk_full_sheds_503_and_server_stays_up(tmp_path):
    """ENOSPC on the WAL path: the write is shed with the typed 503
    mapping, the merged ops stay un-acked, reads keep serving, and the
    disk recovering restores the write path."""
    eng = _durable_engine(tmp_path / "dur")
    ok, _ = _submit(eng, "ddoc", chain_ops(1, 5))
    assert ok
    doc = eng.get("ddoc")
    vals_before = doc.snapshot()
    real_sync = doc.wal.sync
    real_sync_begin = doc.wal.sync_begin

    def enospc(*_a, **_k):
        raise OSError(28, "No space left on device")

    # fail both WAL durability seams: sync() for the single/threaded
    # lanes, sync_begin() for completion-driven backends
    doc.wal.sync = enospc
    doc.wal.sync_begin = enospc
    try:
        with pytest.raises(WalUnavailable):
            _submit(eng, "ddoc", chain_ops(1, 5, start=6))
    finally:
        doc.wal.sync = real_sync
        doc.wal.sync_begin = real_sync_begin
    # server alive: reads serve the last PUBLISHED snapshot, the
    # scheduler thread survived, the shed is counted, and the merge
    # was ROLLED BACK (the log must never hold ops in neither the
    # tiers nor the WAL)
    assert doc.snapshot() == vals_before
    assert doc.tree.log_length == 5
    assert eng.scheduler.is_alive()
    assert eng.counters.snapshot().get("wal_shed_commits", 0) >= 1
    # WalUnavailable maps through the SchedulerStopped → 503 contract
    assert issubclass(WalUnavailable, SchedulerStopped)
    # disk back: writes ack again (the shed delta's ops were merged
    # un-acked; the retry's duplicates absorb)
    ok, _ = _submit(eng, "ddoc", chain_ops(1, 5, start=6))
    assert ok
    assert len(eng.get("ddoc").snapshot()) == 10
    eng.close()


# -- the crash-point matrix (deterministic, in-process) --------------------


# sites where the kill legitimately races the ack: the work that
# crashes runs AFTER ticket resolution.  Serialized: only the matz
# export runs post-ack.  Pipelined: spill/fold/manifest/matz all
# moved to the background maintenance worker, which by construction
# only ever touches fsync-durable (hence ack-resolved) rows.
_POST_ACK_SITES = {
    False: {"mid-matz-write"},
    True: {"mid-matz-write", "mid-spill", "mid-fold",
           "mid-manifest-write", "mid-bg-fold"},
}


def _matrix_params():
    """The full site × {perdoc, shared} × {serial, pipelined} matrix,
    with the ISSUE-12 pipelined expansion's duplicate coverage
    slow-marked (ISSUE 13 tier-1 wall-time satellite): tier-1 keeps
    every SERIAL combo (the pre-expansion coverage), one pipelined
    representative per PIPELINE-ONLY site (the sites that exist
    nowhere else), and one {shared}×{pipelined} representative — the
    remaining pipelined duplicates (same site already proven serial,
    same code path already proven perdoc) run in the slow lane."""
    tier1_pipelined = {(s, False) for s in wal_mod.PIPELINE_ONLY_SITES}
    tier1_pipelined.add((wal_mod.CRASH_SITES[0], True))   # shared rep
    out = []
    for site in wal_mod.CRASH_SITES:
        for shared in (False, True):
            for pipeline in (False, True):
                marks = ()
                if pipeline and (site, shared) not in tier1_pipelined:
                    marks = (pytest.mark.slow,)
                out.append(pytest.param(
                    site, shared, pipeline, marks=marks,
                    id=f"{site}-{'shared' if shared else 'perdoc'}-"
                       f"{'pipelined' if pipeline else 'serial'}"))
    return out


@pytest.mark.parametrize("site,shared,pipeline", _matrix_params())
def test_crash_point_matrix_zero_acked_loss(tmp_path, site, shared,
                                            pipeline, monkeypatch):
    """One kill site per run — × {per-doc, shared} WAL streams × the
    {serialized, pipelined} commit paths: acked writes survive, the
    recovered doc serves immediately at a bumped epoch, windows stay
    byte-identical, and the oracle's convergence check reports zero
    violations over the recovered serving surface.  In-process kill:
    the CrashPoint BaseException stops the thread that hit the site
    exactly there (nothing after it runs — no fsync, no publish, no
    ack on that path), the other pipeline threads die at their next
    check, and everything already ``write()``-en survives in the page
    cache, which is precisely the post-SIGKILL disk state."""
    if not pipeline and site in wal_mod.PIPELINE_ONLY_SITES:
        pytest.skip("site only exists on the pipelined commit path")
    monkeypatch.setenv("GRAFT_OPLOG_GC_SEGS", "1")
    # a tiny materialization cadence so the armed commit also crosses
    # the matz refresh (the mid-matz-write site must actually fire,
    # and every OTHER site now runs with artifact writes in play too)
    monkeypatch.setenv("GRAFT_MATZ_TAIL_OPS", "8")
    ddir = tmp_path / "dur"
    eng = _durable_engine(ddir, submit_timeout_s=2.0,
                          wal_shared=shared, pipeline=pipeline)
    acked = []
    ops = chain_ops(1, 80)
    for i in range(0, 15, 5):
        ok, _ = _submit(eng, "doc", ops[i:i + 5])
        assert ok
        acked.extend(ops[i:i + 5])
    # barrier over the pipeline lanes so the setup writes' background
    # spills/exports are done BEFORE the site arms (the doomed write
    # below must be the one that trips it)
    assert eng.flush(30)
    monkeypatch.setenv("GRAFT_CRASH_POINT", site)
    # a 20-leaf commit from a 15-op log with hot_ops=8 forces spill →
    # fold (gc_min_segs=1) → manifest in the armed commit, so every
    # site fires on this one write
    crashed = {}

    def doomed():
        try:
            crashed["ack"] = _submit(eng, "doc", ops[15:35])
        except SchedulerStopped:
            crashed["ack"] = None

    th = threading.Thread(target=doomed, daemon=True)
    th.start()
    eng.scheduler.join(30)
    assert not eng.scheduler.is_alive(), \
        f"site {site} never fired (scheduler survived)"
    th.join(10)
    if site in _POST_ACK_SITES[pipeline]:
        # post-ack work: the doomed commit's ack legitimately races
        # the crash — and if it landed, it is already fsynced and
        # must survive recovery like any other acked write
        if crashed.get("ack") and crashed["ack"][0]:
            acked.extend(ops[15:35])
    else:
        assert crashed.get("ack") is None, \
            f"site {site}: a write acked AFTER the crash point"
    monkeypatch.delenv("GRAFT_CRASH_POINT")
    # recover from disk (the wounded engine is abandoned, un-closed)
    eng2 = _durable_engine(ddir, wal_shared=shared)
    doc2 = eng2.get("doc", create=False)
    assert doc2 is not None and doc2.epoch == 2
    vals = set(doc2.snapshot())
    missing = [op.value for op in acked if op.value not in vals]
    assert not missing, f"site {site} lost acked writes: {missing}"
    _windows_match_untiered(doc2.tree,
                            sinces=(0, ts(1, 3), ts(1, 13)),
                            limits=(0, 6))
    # oracle contract over the recovered serving surface: two
    # sessions' final reads of the SAME published snapshot converge
    oracle = oracle_mod.SessionOracle()
    snap = doc2.read_view()
    for sess in ("s-a", "s-b"):
        oracle.observe_final_read(sess, "doc", snap.seq,
                                  snap.fingerprint())
        oracle.observe_replica_state("doc", f"n0.{doc2.epoch}",
                                     snap.state_fingerprint())
    assert oracle.finalize() == []
    assert oracle.stats()["violations_total"] == 0
    # serving-ready: the recovered doc accepts writes at once (an
    # independent chain — the doomed batch was never acked, so a
    # write anchored on it would be a legitimate 409)
    ok, _ = _submit(eng2, "doc", chain_ops(9, 3))
    assert ok
    eng2.close()


@pytest.mark.parametrize("site", ["mid-spill", "mid-fold"])
def test_direct_fold_crash_zero_acked_loss(tmp_path, site, monkeypatch):
    """A catch-up-shaped commit (a fresh document's hot tail at least
    ``gc_min_segs`` segments past its budget) seals straight into base
    chunks; a kill at either site between those seals and the manifest
    write leaves the old manifest plus unreferenced chunk files, and
    recovery loses no acked write, serves byte-identical windows, and
    direct-folds again past the strays."""
    ddir = tmp_path / "dur"
    eng = _durable_engine(ddir, submit_timeout_s=2.0)
    ops = chain_ops(1, 80)
    ok, _ = _submit(eng, "doc", ops[:5])
    assert ok
    acked = list(ops[:5])
    assert eng.flush(30)
    monkeypatch.setenv("GRAFT_CRASH_POINT", site)
    crashed = {}

    def doomed():
        try:
            crashed["ack"] = _submit(eng, "doc", ops[5:])
        except SchedulerStopped:
            crashed["ack"] = None

    th = threading.Thread(target=doomed, daemon=True)
    th.start()
    eng.scheduler.join(30)
    assert not eng.scheduler.is_alive(), f"site {site} never fired"
    th.join(10)
    assert crashed.get("ack") is None
    monkeypatch.delenv("GRAFT_CRASH_POINT")
    # the direct fold ran: chunks sealed, no segment, none referenced
    files = os.listdir(ddir / "doc-doc")
    stray = [f for f in files if f.startswith("base-")]
    assert stray and not [f for f in files if f.startswith("seg-")]
    if "manifest.json" in files:
        with open(ddir / "doc-doc" / "manifest.json") as f:
            listed = json.dumps(json.load(f))
        assert not [f for f in stray if f in listed]
    eng2 = _durable_engine(ddir)
    doc2 = eng2.get("doc", create=False)
    assert doc2 is not None and doc2.epoch == 2
    vals = set(doc2.snapshot())
    assert not [op.value for op in acked if op.value not in vals]
    _windows_match_untiered(doc2.tree, sinces=(0, ts(1, 3)),
                            limits=(0, 6))
    # a second catch-up on the recovered doc folds directly again
    log2 = doc2.tree._log
    folds = log2.direct_folds
    ok, _ = _submit(eng2, "doc", chain_ops(9, 80))
    assert ok
    assert eng2.flush(30)
    assert log2.direct_folds == folds + 1
    assert set(doc2.snapshot()) >= vals | {op.value
                                           for op in chain_ops(9, 80)}
    _windows_match_untiered(doc2.tree, sinces=(0, ts(9, 40)),
                            limits=(0, 6))
    eng2.close()


# -- persisted materialization (ISSUE 11) -----------------------------------


def test_matz_corruption_taxonomy(tmp_path):
    """The artifact's failure modes, each the SAFE way: a stale
    artifact dup-absorbs through tail replay, a crc-flipped or
    truncated or missing artifact falls back to the full first merge
    with a typed MatzWarning and a counted fallback — never wrong
    data, never an exception to the reader."""
    import glob
    import warnings

    from crdt_graph_tpu.serve import snapshot as snapshot_mod

    def fresh(dirname, n=900):
        t = engine.init(0)
        t.enable_log_tiering(str(tmp_path / dirname), hot_ops=64,
                             gc_min_segs=2)
        t.apply_packed(packed_mod.pack(chain_ops(1, n), max_depth=4))
        return t

    ref = engine.init(0)
    ref.apply(Batch(tuple(chain_ops(1, 900))))
    want_vals = ref.visible_values()
    want_fp = snapshot_mod.derive("d", 0, ref).state_fingerprint()

    # (a) healthy: first read comes off the artifact, zero fallbacks
    t = fresh("ok")
    t.checkpoint_tiered(str(tmp_path / "ok"))
    r = engine.TpuTree.restore_tiered(str(tmp_path / "ok"))
    assert r.visible_values() == want_vals
    assert r.matz_stats == {"writes": 0, "loads": 1, "fallbacks": 0,
                            "tail_replayed": 0}
    assert snapshot_mod.derive("d", 0, r).state_fingerprint() == want_fp

    # (b) stale: ops landed after the artifact — tail replay absorbs
    t = fresh("stale", n=700)
    assert t.write_matz()
    t.apply(Batch(tuple(chain_ops(1, 200, start=701))))
    t.checkpoint_tiered(str(tmp_path / "stale"), write_matz=False)
    r = engine.TpuTree.restore_tiered(str(tmp_path / "stale"))
    assert r.visible_values() == want_vals
    assert r.matz_stats["loads"] == 1
    assert r.matz_stats["tail_replayed"] == 200
    assert snapshot_mod.derive("d", 0, r).state_fingerprint() == want_fp

    # (c) crc-flip / truncation / missing: typed fallback, right data
    for mode in ("flip", "trunc", "missing"):
        d = f"bad-{mode}"
        t = fresh(d)
        t.checkpoint_tiered(str(tmp_path / d))
        victim = glob.glob(str(tmp_path / d / "matz-*.npz"))[0]
        blob = open(victim, "rb").read()
        if mode == "flip":
            flipped = bytearray(blob)
            flipped[len(flipped) // 2] ^= 0xFF
            open(victim, "wb").write(bytes(flipped))
        elif mode == "trunc":
            open(victim, "wb").write(blob[: len(blob) // 3])
        else:
            os.remove(victim)
        r = engine.TpuTree.restore_tiered(str(tmp_path / d))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            vals = r.visible_values()
        assert any(issubclass(x.category, engine.MatzWarning)
                   for x in w), (mode, [x.category for x in w])
        assert r.matz_stats["fallbacks"] == 1, mode
        assert vals == want_vals, mode
        assert snapshot_mod.derive("d", 0, r).state_fingerprint() \
            == want_fp, mode


def test_matz_overcovering_entry_degrades_never_bricks(tmp_path):
    """A rollback truncate can shrink the TIERED extent below a matz
    artifact's coverage while the entry legitimately survives (the cut
    was above it).  The mid-flight manifest then carries
    matz.len > length — restore must treat that as the lazy fallback
    case (stale-or-unusable artifact, typed warning at worst), never a
    CheckpointError that bricks the document."""
    import warnings

    d = str(tmp_path / "oc")
    t = engine.init(0)
    t.enable_log_tiering(d, hot_ops=32, gc_min_segs=1, durable=True,
                         base_chunk_ops=64)
    t._log.set_durable_hooks(t.manifest_meta, None)
    t.apply_packed(packed_mod.pack(chain_ops(1, 150), max_depth=4))
    assert t.write_matz()
    assert t._log.matz_entry["len"] == 150
    # enough new ops that the next fold MERGES the trailing partial
    # chunk ([128,150)) with fresh segments into one chunk straddling
    # the artifact's coverage boundary
    t.apply_packed(packed_mod.pack(chain_ops(1, 70, start=151),
                                   max_depth=4))
    t._log.maybe_spill()
    assert any(cs.start < 150 < cs.start + cs.length
               for cs in t._log._bases), \
        [(cs.start, cs.length) for cs in t._log._bases]
    # rollback-shaped cut ABOVE the artifact's coverage: the entry
    # survives, but the straddling chunk's prefix re-hots and the
    # durable manifest's length drops below matz.len
    t._log.truncate(160)
    assert t._log.matz_entry is not None
    extent = t._log.tiered_extent
    # the brick only reproduces when the manifest length undercuts the
    # coverage; the chunk layout guarantees it here
    assert extent < 150, extent
    r = engine.TpuTree.restore_tiered(d)     # must not raise
    assert r.log_length == extent
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        vals = r.visible_values()
    ref = engine.init(0)
    ref.apply(Batch(tuple(chain_ops(1, extent))))
    assert vals == ref.visible_values()


def test_recovered_doc_first_read_from_matz_flight_and_prom(
        tmp_path, monkeypatch):
    """Serving-side cold-path collapse: a durable doc refreshes its
    artifact at the GRAFT_MATZ_TAIL_OPS cadence; a restarted engine's
    first read loads it (no full merge), commits stamp ``matz_hit``
    into their flight records, and the crdt_matz_* families render
    under the strict prom contract."""
    monkeypatch.setenv("GRAFT_MATZ_TAIL_OPS", "16")
    ddir = tmp_path / "dur"
    eng = _durable_engine(ddir)
    for i in range(4):
        ok, _ = _submit(eng, "mdoc", chain_ops(1, 12, start=1 + 12 * i))
        assert ok
    doc = eng.get("mdoc")
    vals = doc.snapshot()
    # pipelined: the artifact export rides the maintenance worker —
    # flush() barriers over it (due pickups included) by contract
    assert eng.flush(30)
    assert doc.tree.matz_stats["writes"] >= 1
    assert doc.tree._log.matz_entry is not None
    # abandon un-closed; recover
    eng2 = _durable_engine(ddir)
    doc2 = eng2.get("mdoc", create=False)
    assert doc2 is not None and doc2.recovered
    assert doc2.snapshot() == vals
    assert doc2.tree.matz_stats["loads"] == 1, doc2.tree.matz_stats
    assert doc2.tree.matz_stats["fallbacks"] == 0
    m = doc2.metrics()["matz"]
    assert m["loads"] == 1 and m["len"] > 0
    # a post-recovery commit's flight record stamps the hit
    ok, _ = _submit(eng2, "mdoc", chain_ops(1, 3, start=49))
    assert ok
    rec = [r for r in eng2.flight.records()
           if r.doc_id == "mdoc" and r.outcome == "committed"][-1]
    assert rec.matz_hit is True
    fams = prom_mod.parse_text(eng2.render_prom())
    for fam in ("crdt_matz_writes_total", "crdt_matz_loads_total",
                "crdt_matz_fallbacks_total",
                "crdt_matz_tail_replayed_total",
                "crdt_matz_covered_ops",
                "crdt_oplog_cache_evictions_total"):
        assert fam in fams, fam
    loads = [v for _, lbl, v in
             fams["crdt_matz_loads_total"]["samples"]
             if lbl["doc"] == "mdoc"]
    assert loads == [1.0]
    eng2.close()
    eng.close()


def test_kernel_push_matz_export_builds_no_mirror(tmp_path, monkeypatch):
    """A durable server's round-end artifact export after a kernel push
    reads the slot arrays from the merged table: no host mirror is built
    on the way, and a restarted engine's first read comes off that
    artifact with the same values."""
    monkeypatch.setenv("GRAFT_MATZ_TAIL_OPS", "64")
    ops = chain_ops(1, 700) + chain_ops(2, 700)
    ref = engine.init(0)
    ref.apply(Batch(tuple(ops)))
    ddir = tmp_path / "dur"
    eng = _durable_engine(ddir)
    c0 = engine.view_counts()
    ok, _ = _submit(eng, "kdoc", ops)
    assert ok
    assert eng.flush(30)
    doc = eng.get("kdoc")
    assert doc.tree.matz_stats["writes"] >= 1
    assert doc.tree._log.matz_entry is not None
    assert doc.tree._mirror is None
    c1 = engine.view_counts()
    assert c1["mirror_builds"] == c0["mirror_builds"]
    assert c1["visible_from_table"] > c0["visible_from_table"]
    assert doc.snapshot() == ref.visible_values()
    eng2 = _durable_engine(ddir)
    doc2 = eng2.get("kdoc", create=False)
    assert doc2 is not None and doc2.recovered
    assert doc2.snapshot() == ref.visible_values()
    assert doc2.tree.matz_stats["loads"] == 1, doc2.tree.matz_stats
    assert doc2.tree.matz_stats["fallbacks"] == 0
    eng2.close()
    eng.close()


# -- shared WAL stream (ISSUE 11) --------------------------------------------


def test_shared_wal_one_fsync_covers_whole_round(tmp_path):
    """The amortization headline, deterministically: N documents'
    writes staged under a paused scheduler resolve in ONE round with
    ONE shared fsync covering all of them (per-doc mode pays N), at
    the same fsync-before-ack point."""
    n_docs = 6
    eng = _durable_engine(tmp_path / "dur", wal_shared=True,
                          oplog_hot_ops=4096)
    assert eng.shared_wal is not None
    eng.scheduler.pause()
    results = []

    def writer(k):
        ops = [Add(ts(2 + k, 1), (0,), f"w{k}")]
        results.append(_submit(eng, f"sdoc{k}", ops))

    threads = [threading.Thread(target=writer, args=(k,), daemon=True)
               for k in range(n_docs)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        docs = [eng.get(f"sdoc{k}", create=False)
                for k in range(n_docs)]
        if all(d is not None and len(d.queue) == 1 for d in docs):
            break
        time.sleep(0.005)
    fsyncs0 = eng.shared_wal.telemetry()["fsyncs"]
    eng.scheduler.resume()
    for t in threads:
        t.join(30)
    assert len(results) == n_docs and all(ok for ok, _ in results)
    st = eng.shared_wal.telemetry()
    assert st["fsyncs"] - fsyncs0 == 1, st
    assert st["appends"] >= n_docs
    # the covered-docs histogram saw the whole round at once
    cov = st["covered_docs"]
    assert cov is not None and cov["count"] >= 1
    assert eng.counters.snapshot().get("wal_shared_covered_docs", 0) \
        >= n_docs
    # every doc's commit billed the one fsync into its stages
    for k in range(n_docs):
        rec = [r for r in eng.flight.records()
               if r.doc_id == f"sdoc{k}" and r.outcome == "committed"]
        assert rec and "wal_fsync" in rec[-1].stages_ms
    fams = prom_mod.parse_text(eng.render_prom())
    for fam in ("crdt_wal_shared_fsyncs_total",
                "crdt_wal_shared_appends_total",
                "crdt_wal_shared_covered_docs",
                "crdt_wal_shared_fsync_ms",
                "crdt_wal_shared_size_bytes"):
        assert fam in fams, fam
    eng.close()


def test_shared_wal_failed_sync_after_repair_reopen_drops_tail(
        tmp_path):
    """A repair mid-round closes the handle; the reopen must NOT
    promote the still-unsynced earlier record to durable — when the
    round's fsync then fails, the whole unsynced tail (whose commits
    are all being shed) must drop, or recovery would resurrect a
    write its client was told failed."""
    sh = wal_mod.SharedWal(str(tmp_path / "s.log"))
    sh.append("A", packed_mod.pack(chain_ops(1, 3)), 3)   # unsynced
    # doc B's append dies mid-write: repair truncates the partial
    # bytes and closes the handle
    sh._repair_locked(sh._size)
    # doc C's append reopens the file (A's record still unsynced)
    sh.append("C", packed_mod.pack(chain_ops(2, 3)), 3)
    real = os.fsync

    def eio(fd):
        raise OSError(5, "Input/output error")

    os.fsync = eio
    try:
        with pytest.raises(OSError):
            sh.sync(covered_docs=2)
    finally:
        os.fsync = real
    sh.close()
    # every record in the failed round was shed: none may survive
    records, torn, _ = wal_mod.scan_shared(str(tmp_path / "s.log"))
    assert [(r[1], r[2]) for r in records] == [], records


def test_wal_mode_flip_across_restart_refuses_loudly(tmp_path):
    """Restarting a durable dir under the OTHER WAL format must fail
    with a typed WalError, not silently drop the previous format's
    fsync-acked tail (only the writing format can replay it)."""
    ddir = tmp_path / "dur"
    # per-doc incarnation leaves an un-truncated wal.log tail
    eng = _durable_engine(ddir, oplog_hot_ops=4096)
    ok, _ = _submit(eng, "flip", chain_ops(1, 6))
    assert ok
    assert eng.flush(20)
    with pytest.raises(wal_mod.WalError, match="per-doc WAL"):
        _durable_engine(ddir, wal_shared=True)
    # the honest restart (same mode) still recovers fine
    eng2 = _durable_engine(ddir)
    assert eng2.get("flip", create=False).snapshot() \
        == eng.get("flip").snapshot()
    eng2.close()
    eng.close()
    # and the reverse: a shared incarnation's stream blocks a per-doc
    # restart
    ddir2 = tmp_path / "dur2"
    eng3 = _durable_engine(ddir2, wal_shared=True, oplog_hot_ops=4096)
    ok, _ = _submit(eng3, "flip2", chain_ops(1, 6))
    assert ok
    assert eng3.flush(20)
    with pytest.raises(wal_mod.WalError, match="shared WAL stream"):
        _durable_engine(ddir2)
    eng4 = _durable_engine(ddir2, wal_shared=True)
    assert eng4.get("flip2", create=False).snapshot() \
        == eng3.get("flip2").snapshot()
    eng4.close()
    eng3.close()


def test_shared_wal_disk_full_sheds_all_covered_commits(tmp_path):
    """A failed SHARED fsync sheds and rolls back EVERY commit it
    covered (their records share the dropped unsynced tail) — and the
    disk recovering restores the write path for all of them."""
    eng = _durable_engine(tmp_path / "dur", wal_shared=True)
    for k in range(2):
        ok, _ = _submit(eng, f"fdoc{k}", chain_ops(1, 4))
        assert ok
    real_sync = eng.shared_wal.sync

    def enospc(covered_docs=1):
        raise OSError(28, "No space left on device")

    eng.shared_wal.sync = enospc
    try:
        with pytest.raises(WalUnavailable):
            _submit(eng, "fdoc0", chain_ops(1, 4, start=5))
    finally:
        eng.shared_wal.sync = real_sync
    doc = eng.get("fdoc0")
    assert doc.tree.log_length == 4      # rolled back
    assert eng.scheduler.is_alive()
    ok, _ = _submit(eng, "fdoc0", chain_ops(1, 4, start=5))
    assert ok
    assert doc.tree.log_length == 8
    eng.close()


# -- satellites ------------------------------------------------------------


def test_restore_tiered_preserves_last_operation(tmp_path):
    """ISSUE 9 satellite: checkpoint_tiered/restore_tiered used to
    drop ``last_operation`` silently; the manifest now carries the
    span (or blob), and a restored node reports the same provenance."""
    t = engine.init(0)
    t.apply(Batch(tuple(chain_ops(1, 30))))
    last = t.last_operation
    assert len(last.ops) == 30
    t.checkpoint_tiered(str(tmp_path / "ck"))
    r = engine.TpuTree.restore_tiered(str(tmp_path / "ck"))
    assert r.last_operation == last
    assert len(r.last_operation.ops) == 30

    # bare single-op shape survives too (the reference's bare echo)
    t2 = engine.init(0)
    t2.apply(Batch(tuple(chain_ops(2, 6))))
    bare = Add(ts(2, 7), (ts(2, 6),), "bare")
    t2.apply(bare)
    assert isinstance(t2.last_operation, Add)
    t2.checkpoint_tiered(str(tmp_path / "ck2"))
    r2 = engine.TpuTree.restore_tiered(str(tmp_path / "ck2"))
    assert isinstance(r2.last_operation, Add)
    assert r2.last_operation == bare

    # empty-batch sentinel: a fresh restore-of-restore keeps it
    r2.checkpoint_tiered(str(tmp_path / "ck3"))
    r3 = engine.TpuTree.restore_tiered(str(tmp_path / "ck3"))
    assert r3.last_operation == r2.last_operation


def test_catchup_503_with_priority_pull():
    """ISSUE 9 satellite: a fleet node that doesn't hold a document a
    peer HAS answers 503 + Retry-After + X-Catchup-Remaining (not
    404) and triggers a priority anti-entropy pull that lands without
    waiting out the (dormant) sync interval."""
    from http.client import HTTPConnection

    from crdt_graph_tpu.cluster import FleetServer, MemoryKV

    kv = MemoryKV()
    a = FleetServer("n0", kv, ttl_s=600, ae_interval_s=3600)
    b = FleetServer("n1", kv, ttl_s=600, ae_interval_s=3600)
    try:
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if all(len(fs.node.refresh_ring()) == 2 for fs in (a, b)):
                break
            time.sleep(0.02)
        # a doc primaried on n0, written through n0
        doc = next(d for d in (f"cd{i}" for i in range(64))
                   if a.node.primary_for(d) == "n0")
        conn = HTTPConnection("127.0.0.1", a.port, timeout=30)
        conn.request("POST", f"/docs/{doc}/ops",
                     body=json_codec.dumps(Batch(tuple(chain_ops(1, 6)))))
        assert conn.getresponse().status == 200
        conn.close()
        # n1 knows the doc exists (peer listing) but hasn't pulled it:
        # exactly the restart / new-owner catch-up window
        st = b.node.antientropy._peer_state("n0", a.addr)
        st.known_docs = frozenset({doc})
        conn = HTTPConnection("127.0.0.1", b.port, timeout=30)
        conn.request("GET", f"/docs/{doc}")
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 503, (resp.status, body)
        assert resp.getheader("Retry-After") is not None
        assert resp.getheader("X-Catchup-Remaining") == "1"
        conn.close()
        assert b.node.antientropy.priority_pulls >= 1
        # the priority wake pulls the doc despite the 3600 s interval
        deadline = time.monotonic() + 20
        got = None
        while time.monotonic() < deadline:
            conn = HTTPConnection("127.0.0.1", b.port, timeout=30)
            conn.request("GET", f"/docs/{doc}")
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            if resp.status == 200:
                got = json.loads(body)["values"]
                break
            time.sleep(0.05)
        assert got is not None, "priority pull never landed"
        assert got == [f"v1.{c}" for c in range(1, 7)]
        # an unknown doc is still an honest 404
        conn = HTTPConnection("127.0.0.1", b.port, timeout=30)
        conn.request("GET", "/docs/nosuchdoc")
        assert conn.getresponse().status == 404
        conn.close()
    finally:
        a.stop()
        b.stop()


def test_prom_wal_families_strict_parse(tmp_path):
    eng = _durable_engine(tmp_path / "dur")
    ok, _ = _submit(eng, "pdoc", chain_ops(1, 12))
    assert ok
    text = eng.render_prom()
    fams = prom_mod.parse_text(text)
    for fam in ("crdt_wal_appends_total", "crdt_wal_fsyncs_total",
                "crdt_wal_appended_bytes_total",
                "crdt_wal_truncations_total", "crdt_wal_errors_total",
                "crdt_wal_size_bytes", "crdt_wal_epoch",
                "crdt_wal_fsync_ms"):
        assert fam in fams, fam
    assert fams["crdt_wal_fsync_ms"]["type"] == "histogram"
    # non-durable engines keep their scrape unchanged
    eng2 = ServingEngine(flight=flight_mod.FlightRecorder())
    assert not any(f.startswith("crdt_wal_")
                   for f in prom_mod.parse_text(eng2.render_prom()))
    eng2.close()
    eng.close()


# -- process-level matrix + fleet soak + headline (slow) -------------------


def _proc_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "true"
    return env


@pytest.mark.slow
@pytest.mark.parametrize(
    "site,shared",
    [(s, False) for s in wal_mod.CRASH_SITES]
    + [("ack-pre-fsync", True), ("post-fsync-pre-publish", True),
       ("mid-matz-write", True), ("pre-queue-fsync", True)])
def test_wal_crash_point_process_matrix(tmp_path, site, shared):
    """The real thing: a server process dies by os._exit(137) at the
    armed site mid-HTTP-traffic; a fresh engine recovers the durable
    dir with zero acked-write loss — per-doc WAL at every site, plus
    the shared stream at its own durability boundaries."""
    ddir = str(tmp_path / "dur")
    ack_log = str(tmp_path / "acked.txt")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__),
                      "_wal_crash_worker.py"),
         site, ddir, ack_log] + (["shared"] if shared else []),
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=_proc_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 137, \
        (site, proc.returncode, proc.stdout[-800:], proc.stderr[-800:])
    acked = [ln for ln in open(ack_log).read().splitlines() if ln]
    assert acked, "worker crashed before anything was acked"
    eng = ServingEngine(durable_dir=ddir, wal_sync="batch",
                        wal_shared=shared,
                        flight=flight_mod.FlightRecorder())
    doc = eng.get("crash", create=False)
    assert doc is not None
    vals = set(doc.snapshot())
    missing = [v for v in acked if v not in vals]
    assert not missing, f"site {site} lost acked writes: {missing}"
    assert doc.epoch == 2
    _windows_match_untiered(doc.tree, sinces=(0,), limits=(0, 6))
    eng.close()


@pytest.mark.slow
def test_wal_sigkill_fleet_soak(tmp_path):
    """SIGKILL matrix over the fleet, WAL on: a durable node dies hard
    mid-traffic with acked writes only in its WAL (anti-entropy
    dormant), restarts under its old name, recovers its docs from
    disk, and the fleet converges with every acked value present."""
    import signal

    spool = str(tmp_path / "kv")
    durdirs = {n: str(tmp_path / f"dur-{n}") for n in ("n0", "n1")}
    procs, infos = {}, {}

    def spawn(name, ae_interval):
        proc = subprocess.Popen(
            [sys.executable, "-m", "crdt_graph_tpu.cluster", "--cpu",
             "--name", name, "--kv-dir", spool, "--port", "0",
             "--ttl", "2.0", "--ae-interval", str(ae_interval),
             "--durable-dir", durdirs[name], "--wal-sync", "batch"],
            cwd=os.path.join(os.path.dirname(__file__), ".."),
            env=_proc_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        line = proc.stdout.readline()
        assert line.startswith("READY "), line
        return proc, json.loads(line[len("READY "):])

    def req(port, method, path, body=None, timeout=60):
        from http.client import HTTPConnection
        conn = HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, resp.read(), dict(resp.getheaders())
        finally:
            conn.close()

    try:
        # a LONG anti-entropy interval: the victim's acked writes must
        # survive through its WAL, not through replication
        for n in durdirs:
            procs[n], infos[n] = spawn(n, ae_interval=30.0)
        ports = {n: int(i["addr"].rsplit(":", 1)[1])
                 for n, i in infos.items()}
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            views = {n: json.loads(req(p, "GET", "/cluster")[1])
                     for n, p in ports.items()}
            if all(len(v["members"]) == 2 for v in views.values()):
                break
            time.sleep(0.1)
        # find a doc primaried on n0 and push acked writes to it
        doc = None
        for cand in (f"soak{i}" for i in range(64)):
            st, raw, _ = req(ports["n0"], "POST",
                             f"/docs/{cand}/ops",
                             body=json_codec.dumps(
                                 Batch(tuple(chain_ops(1, 5)))))
            assert st == 200
            if json.loads(raw)["served_by"]["name"] == "n0":
                doc = cand
                break
        assert doc is not None
        acked = [f"v1.{c}" for c in range(1, 6)]
        for k in range(5):
            ops = chain_ops(1, 5, start=6 + 5 * k)
            st, raw, _ = req(ports["n0"], "POST", f"/docs/{doc}/ops",
                             body=json_codec.dumps(Batch(tuple(ops))))
            out = json.loads(raw)
            assert st == 200 and out["accepted"], out
            if out["served_by"]["name"] == "n0":
                acked += [op.value for op in ops]
        # SIGKILL the primary: its acked hot tail exists ONLY in its
        # durable dir (anti-entropy hasn't run)
        procs["n0"].send_signal(signal.SIGKILL)
        procs["n0"].wait(30)
        procs.pop("n0").stdout.close()
        # restart under the old name: recovery-to-serving from disk
        p_new, info_new = spawn("n0", ae_interval=0.2)
        procs["n0"] = p_new
        assert info_new["epoch"] >= 2
        assert doc in info_new["recovered_docs"], info_new
        ports["n0"] = int(info_new["addr"].rsplit(":", 1)[1])
        # the recovered node serves the doc IMMEDIATELY (no 404/503)
        st, raw, hdr = req(ports["n0"], "GET", f"/docs/{doc}")
        assert st == 200
        vals = set(json.loads(raw)["values"])
        missing = [v for v in acked if v not in vals]
        assert not missing, f"SIGKILL lost acked writes: {missing}"
        # and the fleet converges to fingerprint-equal state
        deadline = time.monotonic() + 120
        fps = {}
        while time.monotonic() < deadline:
            fps = {}
            for n, p in ports.items():
                st, raw, hdr = req(p, "GET", f"/docs/{doc}")
                if st == 200:
                    fps[n] = hdr.get("X-State-Fingerprint")
            if len(fps) == 2 and len(set(fps.values())) == 1:
                break
            time.sleep(0.5)
        assert len(set(fps.values())) == 1, fps
    finally:
        for p in procs.values():
            try:
                p.terminate()
            except OSError:
                pass
        for p in procs.values():
            try:
                p.wait(20)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow
def test_bench_coldpath_headline_full(tmp_path):
    """The committed-artifact run (BENCH_COLDPATH_r01_cpu.json shape,
    reduced): restore-to-first-read off the materialization artifact
    beats the full-first-merge path ≥5× with bit-identical
    fingerprints, the chunked base bounds a mid-history window's
    resident bytes, and the shared WAL collapses fsyncs/round on the
    many-doc fleet shape with zero oracle violations."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_coldpath_headline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_coldpath_headline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(out_path=str(tmp_path / "BENCH_COLDPATH_test.json"),
                  n_ops=400_000, restore_rounds=1,
                  fleet_docs=24, fleet_sessions=24, fleet_writes=3,
                  fleet_rounds=1)
    assert out["fingerprints_equal"]
    assert out["restore"]["speedup_to_first_read"] >= 5.0, \
        out["restore"]
    best = out["restore"]["best"]
    assert best["matz"]["matz_stats"]["loads"] == 1
    assert best["matz"]["matz_stats"]["fallbacks"] == 0
    # the chunked base keeps a cold window's resident footprint to its
    # covering chunks (400k ops → ≥3 chunks; monolith holds all)
    cat = out["catchup"]
    assert cat["chunked"]["base_chunks"] >= 3
    assert cat["monolith"]["base_chunks"] == 1
    assert cat["resident_ratio"] <= 0.6, cat
    # shared stream amortizes fsyncs on a multi-doc round (the full
    # 64-doc committed artifact holds the ≥8x headline; the reduced
    # tier-shape gate is looser against 1-core scheduling noise)
    fl = out["fleet"]
    assert fl["best"]["shared"]["violations"] == 0
    assert fl["best"]["perdoc"]["violations"] == 0
    assert fl["fsyncs_per_round_reduction"] >= 2.0, fl
    assert fl["shared_vs_perdoc_throughput"] >= 0.8, fl


@pytest.mark.slow
def test_bench_wal_headline_full(tmp_path):
    """The committed-artifact run (BENCH_WAL_r01_cpu.json shape):
    off/batch/commit legs of the loadgen serving shape, oracle-clean,
    batch fsyncs amortized below commit's, and the batch-vs-off
    acked-throughput regression inside a noise-tolerant bound (the
    committed artifact holds the honest ≤ 25% number; the CPU driver
    box is ±40% run-to-run, so the gate here is looser)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_wal_headline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_wal_headline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(out_path=str(tmp_path / "BENCH_WAL_test.json"),
                  n_sessions=12, writes_per_session=6, rounds=2)
    best = out["best"]
    for mode in ("off", "batch", "commit"):
        assert best[mode]["violations"] == 0
        assert best[mode]["writes_acked"] >= 72
        assert best[mode]["ack_p50_ms"] is not None
    assert best["off"]["wal"]["fsyncs"] == 0
    assert best["batch"]["wal"]["fsyncs"] >= 1
    # group commit amortizes within commits: one record and one fsync
    # per COMMIT, never per ticket (cross-mode fsync counts are not
    # comparable — they track commit counts, which vary with how much
    # coalescing each run's timing produced)
    for mode in ("batch", "commit"):
        w = best[mode]["wal"]
        assert w["fsyncs"] <= best[mode]["writes_acked"], (mode, w)
        assert w["appends"] == w["fsyncs"], (mode, w)
    assert out["batch_vs_off_regression"] <= 0.5
