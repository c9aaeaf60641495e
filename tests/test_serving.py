"""Serving engine: snapshot-isolated reads, coalescing scheduler,
admission control, one-launch merges, cross-document batched launches.

The acceptance pins: readers see complete, monotonically advancing
snapshots with sub-10ms latency while a bulk merge commits; a push split
over several applies equals one apply bit for bit; a full queue answers
429 with Retry-After; fused batches attribute per-request outcomes
exactly like sequential application.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
# subset runs must not depend on another test module's global enable
jax.config.update("jax_enable_x64", True)

import crdt_graph_tpu as crdt                          # noqa: E402
from crdt_graph_tpu import engine as engine_mod        # noqa: E402
from crdt_graph_tpu.codec import json_codec            # noqa: E402
from crdt_graph_tpu.codec import packed as packed_mod  # noqa: E402
from crdt_graph_tpu.core import operation as op_mod    # noqa: E402
from crdt_graph_tpu.core.operation import Add, Batch   # noqa: E402
from crdt_graph_tpu.serve import (QueueFull, SchedulerError,  # noqa: E402
                                  SchedulerStopped, ServingEngine)
from crdt_graph_tpu.service.store import Document      # noqa: E402

OFFSET = 2**32


def chain_ops(rid, n, counter0=0, anchor=0):
    """n causally ordered adds from replica ``rid``, chained after
    ``anchor``."""
    ops, prev = [], anchor
    for i in range(n):
        ts = rid * OFFSET + counter0 + i + 1
        ops.append(Add(ts, (prev,), (counter0 + i) & 0xFF))
        prev = ts
    return ops


def submit_async(engine, doc_id, body):
    """Fire a submit from a worker thread; returns (thread, result box)."""
    box = {}

    def go():
        try:
            box["result"] = engine.submit(doc_id, body)
        except BaseException as e:          # noqa: BLE001 — test capture
            box["error"] = e

    th = threading.Thread(target=go, daemon=True)
    th.start()
    return th, box


def wait_queue_depth(engine, doc_id, depth, timeout=10.0):
    doc = engine.get(doc_id)
    deadline = time.monotonic() + timeout
    while len(doc.queue) < depth:
        assert time.monotonic() < deadline, \
            f"queue never reached depth {depth} (at {len(doc.queue)})"
        time.sleep(0.002)


# -- snapshot isolation ----------------------------------------------------


def _reader_soak(n_merge_ops, reader_seconds_after=0.0):
    """N reader threads assert every observed snapshot is complete and
    monotone while a bulk chain merge commits; returns reader latencies
    (ms) observed STRICTLY while the merge was in flight."""
    engine = ServingEngine()
    try:
        engine.submit("soak", json_codec.dumps(
            Batch(tuple(chain_ops(1, 8)))))
        doc = engine.get("soak")
        stop = threading.Event()
        merging = threading.Event()
        failures = []
        lat_ms = []
        lock = threading.Lock()

        def reader():
            last_seq = -1
            local = []
            while not stop.is_set():
                t0 = time.perf_counter()
                snap = doc.snapshot_view()
                n_vals = len(snap.values)
                seq = snap.seq
                dt = (time.perf_counter() - t0) * 1e3
                if merging.is_set():
                    local.append(dt)
                if seq < last_seq:
                    failures.append(f"seq regressed {last_seq}->{seq}")
                    break
                last_seq = seq
                # chain workload: every committed snapshot has exactly
                # as many visible values as applied ops — a torn or
                # half-merged view cannot satisfy this
                if n_vals != snap.log_length:
                    failures.append(
                        f"incomplete snapshot: {n_vals} values for "
                        f"{snap.log_length} ops (seq {seq})")
                    break
            with lock:
                lat_ms.extend(local)

        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(4)]
        for r in readers:
            r.start()
        big = Batch(tuple(chain_ops(2, n_merge_ops)))
        merging.set()
        t0 = time.perf_counter()
        accepted, _ = engine.submit("soak", json_codec.dumps(big))
        merge_s = time.perf_counter() - t0
        merging.clear()
        assert accepted
        if reader_seconds_after:
            time.sleep(reader_seconds_after)
        stop.set()
        for r in readers:
            r.join(10)
        assert not failures, failures[:3]
        snap = doc.snapshot_view()
        assert snap.log_length == n_merge_ops + 8
        assert len(snap.values) == n_merge_ops + 8
        return lat_ms, merge_s
    finally:
        engine.close()


def test_concurrent_readers_during_merge_soak():
    """Readers never block on (or observe) an in-flight merge: while a
    catch-up merge commits, every read returns a complete, monotonically
    advancing snapshot, p99 under 10 ms.  (140k ops, past the grouping
    cap; the slow 1M variant below holds the acceptance scale within the
    tier-1 budget.)"""
    lat_ms, _ = _reader_soak(140_000)
    assert lat_ms, "no reads observed during the merge window"
    lat_ms.sort()
    p99 = lat_ms[(99 * len(lat_ms)) // 100 - 1] if len(lat_ms) >= 100 \
        else lat_ms[-1]
    assert p99 < 10.0, f"reader p99 {p99:.3f} ms during merge"


@pytest.mark.slow
def test_concurrent_readers_during_million_op_merge():
    """The acceptance-scale soak: a 1M-op merge commits while readers
    stay sub-10ms."""
    lat_ms, merge_s = _reader_soak(1_000_000)
    lat_ms.sort()
    p99 = lat_ms[(99 * len(lat_ms)) // 100 - 1]
    assert p99 < 10.0, f"reader p99 {p99:.3f} ms during 1M merge"


def test_snapshot_isolated_reads_are_frozen():
    """A held snapshot keeps answering consistently after later commits
    (readers resolve against the value they loaded, not the live doc)."""
    engine = ServingEngine()
    try:
        engine.submit("frozen", json_codec.dumps(
            Batch(tuple(chain_ops(1, 10)))))
        doc = engine.get("frozen")
        held = doc.snapshot_view()
        vals0 = held.visible_values()
        clock0 = held.clock_wire()
        since0 = held.ops_since_bytes(0)
        engine.submit("frozen", json_codec.dumps(
            Batch(tuple(chain_ops(1, 10, counter0=10,
                                  anchor=1 * OFFSET + 10)))))
        assert doc.snapshot_view().log_length == 20
        # the held snapshot is untouched by the commit
        assert held.visible_values() == vals0
        assert held.clock_wire() == clock0
        assert held.ops_since_bytes(0) == since0
        assert held.log_length == 10
    finally:
        engine.close()


# -- one-launch merges ------------------------------------------------------


def _tree_fingerprint(t):
    """Everything the merge result determines, as comparable values."""
    p = t.packed_state()
    n = p.num_ops
    cols = {k: np.asarray(v)[:n] for k, v in p.arrays().items()}
    return (t.visible_values(), t.timestamp, dict(t._replicas),
            t.log_length, {k: v.tobytes() for k, v in cols.items()})


def test_split_push_bit_identical_to_single_shot():
    """A push applied over several apply_packed calls == one call, bit
    for bit: same column bytes, same clocks, same visible sequence —
    only the log's segment split differs."""
    ops = chain_ops(2, 9000) + chain_ops(3, 9000)
    p = packed_mod.pack(ops)
    one = engine_mod.init(0)
    one.apply_packed(p)
    split = engine_mod.init(0)
    masks = []
    for s in range(0, p.num_ops, 2048):
        split.apply_packed(packed_mod.select_rows(
            p, np.arange(s, min(s + 2048, p.num_ops))))
        masks.append(split.last_applied_mask)
    f1, f2 = _tree_fingerprint(one), _tree_fingerprint(split)
    assert f1[0] == f2[0] and f1[1] == f2[1] and f1[2] == f2[2] \
        and f1[3] == f2[3]
    assert f1[4] == f2[4], "column bytes diverged"
    assert np.array_equal(one.last_applied_mask, np.concatenate(masks))


def test_bulk_apply_packed_atomic_rollback():
    """A failing kernel-size apply_packed leaves the tree exactly as
    before the call, and raises what apply() raises on the same ops."""
    good = chain_ops(2, 3000)
    bad = good[:2500] + [Add(9 * OFFSET + 1, (123456789,), "orphan")]
    p = packed_mod.pack(bad)
    t = engine_mod.init(0)
    t.apply_packed(packed_mod.pack(chain_ops(1, 50)))
    assert t.packed_route(p.num_ops)
    before = _tree_fingerprint(t)
    with pytest.raises(crdt.OperationFailedError) as packed_err:
        t.apply_packed(p)
    assert _tree_fingerprint(t) == before
    ref = engine_mod.init(0)
    ref.apply_packed(packed_mod.pack(chain_ops(1, 50)))
    with pytest.raises(crdt.OperationFailedError) as object_err:
        ref.apply(Batch(tuple(bad)))
    assert str(packed_err.value) == str(object_err.value)


def test_serving_engine_big_push_one_launch():
    """A push larger than the engine's grouping cap commits in one
    kernel launch and publishes what a single apply_packed gives."""
    from crdt_graph_tpu.obs import flight as flight_mod
    from crdt_graph_tpu.utils import profiling
    ops = chain_ops(2, 6000)
    rec = flight_mod.FlightRecorder()
    engine = ServingEngine(group_max_ops=1024, flight=rec)
    c0 = profiling.span_stats().get("engine.launch", {"count": 0})["count"]
    try:
        engine.submit("d", json_codec.dumps(Batch(tuple(ops))))
        snap = engine.get("d").snapshot_view()
        assert engine.get("d").chunks_launched == 1
    finally:
        engine.close()
    assert profiling.span_stats()["engine.launch"]["count"] - c0 == 1
    (r,) = rec.records()
    assert r.chunk_count == 1
    ref = engine_mod.init(0)
    ref.apply_packed(packed_mod.pack(ops))
    assert list(snap.values) == ref.visible_values()
    assert snap.clock == ref._replicas


def test_push_under_an_eighth_of_the_log_takes_one_launch(monkeypatch):
    """A push of n ops onto a log of L rows with n >= max(1024, L/8),
    whose 1024-op pieces are each under L/8, merges in ONE kernel
    launch: no piece goes down the per-op host path."""
    from crdt_graph_tpu.utils import profiling
    base, n = 16384, 2048
    ops = chain_ops(2, base + n)
    engine = ServingEngine()
    try:
        engine.submit("d", json_codec.dumps(Batch(tuple(ops[:base]))))
        tree = engine.get("d").tree
        assert tree.packed_route(n) and not tree.packed_route(1024)

        def no_host(*a, **k):
            raise AssertionError("push took the host path")

        monkeypatch.setattr(tree, "_apply_host", no_host)
        c0 = profiling.span_stats().get(
            "engine.launch", {"count": 0})["count"]
        accepted, _ = engine.submit("d", json_codec.dumps(
            Batch(tuple(ops[base:]))))
        launches = profiling.span_stats()["engine.launch"]["count"] - c0
        assert accepted and launches == 1
        assert engine.get("d").chunks_launched == 2
        snap = engine.get("d").snapshot_view()
    finally:
        engine.close()
    ref = engine_mod.init(0)
    ref.apply_packed(packed_mod.pack(ops))
    assert list(snap.values) == ref.visible_values()


# -- admission control -----------------------------------------------------


def test_queue_full_raises_and_shutdown_unblocks():
    engine = ServingEngine(start=False, max_queue_requests=2)
    body = json_codec.dumps(Batch(tuple(chain_ops(1, 3))))
    th1, b1 = submit_async(engine, "q", body)
    th2, b2 = submit_async(engine, "q", body)
    wait_queue_depth(engine, "q", 2)
    with pytest.raises(QueueFull) as ei:
        engine.submit("q", body)
    assert ei.value.retry_after_s >= 1
    assert engine.get("q").admission_rejected == 1
    # shutdown resolves the blocked submitters instead of hanging them
    engine.close()
    th1.join(10)
    th2.join(10)
    assert isinstance(b1.get("error"), SchedulerStopped)
    assert isinstance(b2.get("error"), SchedulerStopped)


def test_queue_full_http_429_with_retry_after():
    """The wire face of backpressure: 429, Retry-After header, JSON
    error body — without touching the document tree."""
    from http.client import HTTPConnection
    from crdt_graph_tpu.service import make_server

    engine = ServingEngine(start=False, max_queue_requests=0)
    srv = make_server(port=0, store=engine)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = HTTPConnection("127.0.0.1", srv.server_port, timeout=30)
        conn.request("POST", "/docs/busy/ops",
                     body='{"op":"add","path":[0],"ts":4294967297,'
                          '"val":"a"}')
        resp = conn.getresponse()
        body = json.loads(resp.read().decode())
        assert resp.status == 429
        assert int(resp.getheader("Retry-After")) >= 1
        assert "retry_after_s" in body
        conn.close()
    finally:
        srv.shutdown()
        srv.server_close()
        engine.close()


# -- coalescing ------------------------------------------------------------


def test_coalesced_pushes_match_sequential_document():
    """Five concurrent deltas (with cross-delta duplicates) fused into
    one commit produce the same document and counters as sequential
    application, and each request gets its own applied/dup attribution."""
    r2, r3 = chain_ops(2, 40), chain_ops(3, 40)
    deltas = [
        Batch(tuple(r2)),
        Batch(tuple(r3)),
        Batch(tuple(r2[:10])),                       # pure duplicate
        Batch(tuple(chain_ops(4, 25))),
        Batch(()),                                   # empty delta
    ]
    bodies = [json_codec.dumps(d) for d in deltas]

    engine = ServingEngine()
    try:
        engine.get("co")
        engine.scheduler.pause()
        # enqueue ORDER is load-bearing here (first-arrival-wins dedup
        # attributes the cross-delta duplicates to whichever request is
        # earlier in the queue), so serialize the submits — five racing
        # threads reach the queue in nondeterministic order and the
        # expected counts below assume list order
        pairs = []
        for i, b in enumerate(bodies):
            pairs.append(submit_async(engine, "co", b))
            wait_queue_depth(engine, "co", i + 1)
        engine.scheduler.resume()
        for th, _ in pairs:
            th.join(30)
        results = [b["result"] for _, b in pairs]
        assert all(acc for acc, _ in results)
        counts = [op_mod.count(applied) if applied is not None else 0
                  for _, applied in results]
        assert counts == [40, 40, 0, 25, 0]
        doc = engine.get("co")
        assert engine.counters.get("fused_batches") >= 1
        assert doc.ops_merged == 105 and doc.dup_absorbed == 10

        ref = Document("ref")
        for b in bodies:
            ref.apply_body(b)
        assert doc.snapshot() == ref.tree.visible_values()
        assert doc.clock() == {str(k): v
                               for k, v in ref.tree._replicas.items()}
    finally:
        engine.close()


def test_fused_rejection_attributes_only_guilty_request():
    """A causality-gap delta co-batched with valid deltas 409s alone:
    the valid ones commit (sequential fallback), only the orphan is
    rejected."""
    good1 = json_codec.dumps(Batch(tuple(chain_ops(2, 30))))
    orphan = json_codec.dumps(crdt.Add(7 * OFFSET + 1, (987654321,), "x"))
    good2 = json_codec.dumps(Batch(tuple(chain_ops(3, 30))))

    engine = ServingEngine()
    try:
        engine.get("fr")
        engine.scheduler.pause()
        pairs = [submit_async(engine, "fr", b)
                 for b in (good1, orphan, good2)]
        wait_queue_depth(engine, "fr", 3)
        engine.scheduler.resume()
        for th, _ in pairs:
            th.join(30)
        accs = [b["result"][0] for _, b in pairs]
        assert accs == [True, False, True]
        doc = engine.get("fr")
        assert doc.batches_rejected == 1
        assert doc.ops_merged == 60
        assert engine.counters.get("sequential_fallbacks") >= 1
        assert len(doc.snapshot()) == 60
    finally:
        engine.close()


# -- cross-document batched launch ----------------------------------------


def _push_staged(engine, doc_bodies):
    """Stage one delta per doc with the scheduler stopped, run one
    scheduling round synchronously, resolve all."""
    pairs = []
    for doc_id, body in doc_bodies:
        engine.get(doc_id)
        pairs.append(submit_async(engine, doc_id, body))
    for doc_id, _ in doc_bodies:
        wait_queue_depth(engine, doc_id, 1)
    assert engine.scheduler.step() == len(doc_bodies)
    for th, box in pairs:
        th.join(30)
        assert box["result"][0], "staged push rejected"


def test_cross_doc_batched_launch_matches_per_doc():
    """Three documents' kernel merges in one vmapped launch produce the
    same documents as per-doc launches, and later merges on top of the
    batched commit keep working."""
    n = 1500   # above the kernel crossover (4 * DELTA_THRESHOLD)
    bodies1 = [(f"x{i}", json_codec.dumps(
        Batch(tuple(chain_ops(i + 2, n))))) for i in range(3)]
    bodies2 = [(f"x{i}", json_codec.dumps(
        Batch(tuple(chain_ops(i + 2, n, counter0=n,
                              anchor=(i + 2) * OFFSET + n)))))
               for i in range(3)]

    batched = ServingEngine(start=False, cross_doc=True)
    plain = ServingEngine(start=False, cross_doc=False)
    try:
        _push_staged(batched, bodies1)
        assert batched.counters.get("cross_doc_batches") == 1
        assert batched.counters.get("cross_doc_docs") == 3
        _push_staged(plain, bodies1)
        assert plain.counters.get("cross_doc_batches") == 0
        for doc_id, _ in bodies1:
            assert batched.get(doc_id).snapshot() == \
                plain.get(doc_id).snapshot()
            assert batched.get(doc_id).clock() == \
                plain.get(doc_id).clock()
        # second wave lands on the batched-committed state (n0 > 0)
        _push_staged(batched, bodies2)
        _push_staged(plain, bodies2)
        for doc_id, _ in bodies2:
            assert batched.get(doc_id).snapshot() == \
                plain.get(doc_id).snapshot()
            assert len(batched.get(doc_id).snapshot()) == 2 * n
    finally:
        batched.close()
        plain.close()


# -- snapshot wire formats -------------------------------------------------


def test_snapshot_checkpoint_bytes_bootstrap():
    """A serving snapshot's /snapshot bytes restore under a new replica
    id, and its /ops bytes match the engine's own egress encoder."""
    import io

    engine = ServingEngine()
    try:
        engine.submit("boot", json_codec.dumps(
            Batch(tuple(chain_ops(1, 500)))))
        doc = engine.get("boot")
        blob = doc.snapshot_packed()
        t = engine_mod.TpuTree.restore_packed(io.BytesIO(blob), replica=9)
        assert t.visible_values() == doc.snapshot()
        assert t.replica_id == 9
        # /ops parity with the live-tree encoder
        ref = engine_mod.init(0)
        ref.apply(json_codec.loads(json_codec.dumps(
            Batch(tuple(chain_ops(1, 500))))))
        assert doc.dumps_since_bytes(0) == ref.dumps_since_bytes(0)
        mid = OFFSET + 250
        assert doc.dumps_since_bytes(mid) == ref.dumps_since_bytes(mid)
    finally:
        engine.close()


def test_scheduler_infrastructure_error_surfaces_as_scheduler_error():
    """A non-CRDT failure inside the scheduler resolves the waiting
    request with SchedulerError (the handler's 500) — never a hang,
    never a client-error class — and the scheduler survives for the
    next request."""
    engine = ServingEngine()
    try:
        engine.submit("err", json_codec.dumps(
            Batch(tuple(chain_ops(1, 5)))))
        doc = engine.get("err")
        real = doc.tree.apply_packed

        def boom(*a, **k):
            raise RuntimeError("injected launch failure")

        doc.tree.apply_packed = boom
        with pytest.raises(SchedulerError) as ei:
            engine.submit("err", json_codec.dumps(
                Batch(tuple(chain_ops(1, 5, counter0=5,
                                      anchor=OFFSET + 5)))))
        assert isinstance(ei.value.__cause__, RuntimeError)
        assert engine.counters.get("scheduler_errors") == 1
        # scheduler survived: the next submit merges normally
        doc.tree.apply_packed = real
        accepted, _ = engine.submit("err", json_codec.dumps(
            Batch(tuple(chain_ops(1, 5, counter0=5,
                                  anchor=OFFSET + 5)))))
        assert accepted and len(doc.snapshot()) == 10
    finally:
        engine.close()


def test_scheduler_metrics_surface():
    engine = ServingEngine()
    try:
        engine.submit("m", json_codec.dumps(
            Batch(tuple(chain_ops(1, 20)))))
        m = engine.get("m").metrics()
        for key in ("ops_merged", "queue_depth", "queue_leaves",
                    "admission_rejected", "snapshot_seq",
                    "snapshot_age_s", "chunks_launched",
                    "commit_latency_ms", "coalesce_width"):
            assert key in m, key
        assert m["snapshot_seq"] >= 1
        assert m["commit_latency_ms"]["count"] >= 1
        sm = engine.scheduler_metrics()
        assert "spans" in sm and "queue_depth_total" in sm
        assert any(k.startswith("serve.") for k in sm["spans"])
    finally:
        engine.close()
