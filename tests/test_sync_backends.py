"""Sync-backend identity guard (ISSUE 17; docs/DURABILITY.md §Sync
backends): the completion-driven fsync lanes are a PERFORMANCE fork,
never a semantics fork.  Same workload, same WAL stream bytes, same
recovery results, and the same in-process crash-matrix outcomes across
``GRAFT_WAL_SYNC_BACKEND=single|workers|uring`` — the uring leg
auto-skips (counted, not silent) where the kernel lacks io_uring.
"""
import os
import threading

import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu import wal as wal_mod
from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.core.operation import Add, Batch
from crdt_graph_tpu.obs import flight as flight_mod
from crdt_graph_tpu.obs import prom as prom_mod
from crdt_graph_tpu.serve import SchedulerStopped, ServingEngine
from crdt_graph_tpu.utils import uring as uring_mod

OFF = 2**32

BACKENDS = ("single", "workers", "uring")


def _skip_unless_available(backend):
    if backend == "uring" and not uring_mod.available():
        pytest.skip("kernel lacks io_uring fsync support")


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


def _engine(ddir, backend, **kw):
    # wal_sync="batch" + the pipelined scheduler is the ONLY shape
    # that runs the group-commit fan-out (engine.py constructs the
    # WalSyncWorker exactly there) — "commit" fsyncs inline on the
    # scheduler and would silently ignore the backend under test
    kw.setdefault("oplog_hot_ops", 8)
    kw.setdefault("flight", flight_mod.FlightRecorder())
    kw.setdefault("pipeline", True)
    return ServingEngine(durable_dir=str(ddir), wal_sync="batch",
                         wal_sync_backend=backend, **kw)


def _wal_streams(ddir):
    """doc-relative WAL path -> file bytes, for every stream on disk."""
    out = {}
    for root, _dirs, files in os.walk(ddir):
        for f in files:
            if f.endswith(".log"):
                p = os.path.join(root, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, ddir)] = fh.read()
    return out


def _run_workload(ddir, backend):
    """Serial acked submits (one commit per round — deterministic
    record boundaries) across two docs; returns (wal streams,
    recovered values per doc, recovered state fingerprints)."""
    eng = _engine(ddir, backend)
    for i in range(0, 20, 5):
        ok, _ = _submit(eng, "docA", chain_ops(1, 5, start=i + 1))
        assert ok
        ok, _ = _submit(eng, "docB", chain_ops(2, 5, start=i + 1))
        assert ok
    assert eng.flush(30)
    # the WAL prefix a spill covers is dropped at the NEXT fsync
    # (ServedDoc.wal_mark_durable), so whether the LAST spill's prefix
    # is gone at close depends on timing, not on the backend: settle
    # it the way the scheduler's next fsync would, then compare
    for d in ("docA", "docB"):
        eng.get(d, create=False).wal_mark_durable()
    eng.close()
    streams = _wal_streams(ddir)
    eng2 = _engine(ddir, "single")
    state = {}
    for d in ("docA", "docB"):
        doc = eng2.get(d, create=False)
        assert doc is not None and doc.recovered
        snap = doc.read_view()
        state[d] = (tuple(doc.snapshot()), snap.state_fingerprint())
    eng2.close()
    return streams, state


def test_wal_stream_bytes_and_recovery_identical_across_backends(
        tmp_path):
    """The byte-level guard: every backend lands the IDENTICAL WAL
    stream for the same acked workload (fan-out reorders fsyncs, never
    appends), and recovery reproduces identical values + replica-
    independent state fingerprints."""
    results = {}
    for backend in BACKENDS:
        if backend == "uring" and not uring_mod.available():
            continue
        results[backend] = _run_workload(tmp_path / backend, backend)
    assert "single" in results and "workers" in results
    base_streams, base_state = results["single"]
    assert base_streams, "workload produced no WAL streams"
    for backend, (streams, state) in results.items():
        assert streams == base_streams, \
            f"backend {backend}: WAL stream bytes diverged"
        assert state == base_state, \
            f"backend {backend}: recovered state diverged"
    if "uring" not in results:
        pytest.skip("identity held for single|workers; "
                    "kernel lacks io_uring — uring leg not run")


def _crash_once(ddir, backend, site, monkeypatch):
    """Arm one in-process crash site under ``backend``, recover, and
    return the recovered doc's (values, state fingerprint, epoch)."""
    monkeypatch.setenv("GRAFT_OPLOG_GC_SEGS", "1")
    monkeypatch.setenv("GRAFT_MATZ_TAIL_OPS", "8")
    eng = _engine(ddir, backend, submit_timeout_s=2.0)
    ops = chain_ops(1, 60)
    acked = []
    for i in range(0, 15, 5):
        ok, _ = _submit(eng, "doc", ops[i:i + 5])
        assert ok
        acked.extend(ops[i:i + 5])
    assert eng.flush(30)
    monkeypatch.setenv("GRAFT_CRASH_POINT", site)
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
        f"{backend}/{site}: site never fired"
    th.join(10)
    monkeypatch.delenv("GRAFT_CRASH_POINT")
    eng2 = _engine(ddir, "single")
    doc2 = eng2.get("doc", create=False)
    assert doc2 is not None and doc2.epoch == 2
    vals = set(doc2.snapshot())
    missing = [op.value for op in acked if op.value not in vals]
    assert not missing, \
        f"{backend}/{site} lost acked writes: {missing}"
    acked_vals = {op.value for op in acked}
    out = (tuple(sorted(v for v in vals if v in acked_vals)),
           doc2.epoch)
    eng2.close()
    # the crashed engine's other lanes (WAL sync, maintenance, reactor)
    # are still running: stop them, or every later test in this process
    # inherits their threads
    eng.close(timeout=5.0)
    return out


@pytest.mark.parametrize("site", [
    s for s in wal_mod.CRASH_SITES if s != "mid-matz-write"])
def test_crash_matrix_identical_across_backends(tmp_path, site,
                                                monkeypatch):
    """Each crash site, run under every available backend: zero acked
    loss everywhere, and the recovered ACKED state is identical across
    backends (post-crash-point residue may legitimately differ — a
    faster lane can have fsynced the doomed round before the site
    fired — but nothing acked may diverge).  mid-matz-write is
    excluded here only because its firing depends on a refresh cadence
    the per-backend timing legitimately shifts; the per-backend matrix
    in test_wal.py still covers it."""
    outcomes = {}
    for backend in BACKENDS:
        if backend == "uring" and not uring_mod.available():
            continue
        outcomes[backend] = _crash_once(tmp_path / backend, backend,
                                        site, monkeypatch)
    assert "single" in outcomes and "workers" in outcomes
    base = outcomes["single"]
    for backend, got in outcomes.items():
        assert got == base, (f"site {site}: backend {backend} "
                             f"recovered different acked state")
    if "uring" not in outcomes:
        pytest.skip(f"site {site} held for single|workers; "
                    "kernel lacks io_uring — uring leg not run")


@pytest.mark.parametrize("backend", BACKENDS)
def test_prom_sync_backend_families_strict_parse(tmp_path, backend):
    """`crdt_wal_sync_backend` + `crdt_wal_sync_inflight` render under
    the strict parser with the ACTIVE backend labeled, and are ABSENT
    (same gating as crdt_wal_*) on a non-durable engine."""
    _skip_unless_available(backend)
    eng = _engine(tmp_path / "dur", backend)
    ok, _ = _submit(eng, "doc", chain_ops(1, 5))
    assert ok
    fams = prom_mod.parse_text(eng.render_prom())
    assert "crdt_wal_sync_backend" in fams
    assert "crdt_wal_sync_inflight" in fams
    samples = fams["crdt_wal_sync_backend"]["samples"]
    active = eng.sync_worker.stats()["backend"]
    assert any(lb.get("backend") == active
               for _n, lb, _v in samples), samples
    eng.close()
    eng2 = ServingEngine(oplog_hot_ops=8)
    fams2 = prom_mod.parse_text(eng2.render_prom())
    assert "crdt_wal_sync_backend" not in fams2
    assert "crdt_wal_sync_inflight" not in fams2
    eng2.close()
