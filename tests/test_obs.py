"""Observability subsystem (ISSUE 5): end-to-end commit tracing, the
flight recorder, and the unified telemetry exposition.

Acceptance pins:

- a soak drives concurrent pushes through ``ServingEngine`` and every
  commit record in the flight recorder carries ≥1 trace_id minted at
  admission (and the union of records covers every submitted id);
- a deliberately slow (SLO-breaching) commit and an injected audit
  failure each produce a JSONL dump containing the full stage
  breakdown;
- ``/metrics/prom`` parses with consistent counter/histogram naming
  (strict parser: ``crdt_`` namespace, counters end ``_total``,
  cumulative ``le`` buckets ending ``+Inf``).

Plus the satellite pins: multi-threaded observe/snapshot races on the
serve metrics, histogram bucket-bound exposition, and ring-buffer
wraparound/dump-trigger behavior.
"""
import json
import os
import threading
import time

import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.codec import json_codec                  # noqa: E402
from crdt_graph_tpu.core.operation import Add, Batch         # noqa: E402
from crdt_graph_tpu.obs import flight as flight_mod          # noqa: E402
from crdt_graph_tpu.obs import prom as prom_mod              # noqa: E402
from crdt_graph_tpu.obs.trace import ensure_trace_id, \
    mint_trace_id                                            # noqa: E402
from crdt_graph_tpu.serve import SchedulerError, ServingEngine  # noqa: E402
from crdt_graph_tpu.serve.metrics import Counters, Histogram  # noqa: E402

OFFSET = 2**32


def chain_ops(rid, n, counter0=0, anchor=0):
    ops, prev = [], anchor
    for i in range(n):
        ts = rid * OFFSET + counter0 + i + 1
        ops.append(Add(ts, (prev,), (counter0 + i) & 0xFF))
        prev = ts
    return ops


def mk_recorder(tmp_path, **kw):
    kw.setdefault("capacity", 64)
    kw.setdefault("slo_ms", 60_000.0)
    kw.setdefault("audit_every", 0)
    kw.setdefault("dump_dir", str(tmp_path))
    kw.setdefault("min_dump_interval_s", 0.0)
    return flight_mod.FlightRecorder(**kw)


def base_rec(**over):
    """Minimal record-field dict for direct FlightRecorder.record."""
    rec = {
        "doc_id": "d", "trace_ids": ("t" * 16,), "outcome": "committed",
        "num_ops": 1, "applied_ops": 1, "dup_ops": 0,
        "coalesce_width": 1, "chunk_count": 1,
        "queue_depth_admission": 0,
        "stages_ms": {"parse": 0.1, "merge": 0.2, "publish": 0.1},
        "total_ms": 0.5, "staleness_s": 0.01, "snapshot_seq": 1,
        "fingerprint": "abcd", "audit": None, "error": None,
    }
    rec.update(over)
    return rec


# -- trace ids -------------------------------------------------------------


def test_trace_id_mint_and_adopt():
    a, b = mint_trace_id(), mint_trace_id()
    assert a != b and len(a) == 16
    # well-formed client ids are adopted verbatim
    assert ensure_trace_id("client-trace-42") == "client-trace-42"
    # malformed / missing ids are re-minted (they land in filenames
    # and label values)
    assert ensure_trace_id(None) != ensure_trace_id(None)
    assert ensure_trace_id("short") != "short"
    assert ensure_trace_id("x" * 65) != "x" * 65
    assert ensure_trace_id('bad"quote__') != 'bad"quote__'


# -- serve metrics under concurrency (satellite) ---------------------------


def test_histogram_concurrent_observe_snapshot_race():
    """8 observer threads race snapshot/export readers; no exception,
    no lost updates: the final exported count/sum account for every
    observation."""
    h = Histogram((1, 2, 4, 8))
    n_threads, per_thread = 8, 2000
    stop = threading.Event()
    errors = []

    def observer():
        for i in range(per_thread):
            h.observe(float(i % 10))

    def reader():
        while not stop.is_set():
            snap = h.snapshot()
            exp = h.export()
            try:
                assert sum(exp["counts"]) == exp["count"]
                if snap["count"]:
                    assert snap["sum"] >= 0
            except AssertionError as e:    # noqa: PERF203
                errors.append(str(e))
                return

    readers = [threading.Thread(target=reader) for _ in range(2)]
    observers = [threading.Thread(target=observer)
                 for _ in range(n_threads)]
    for t in readers + observers:
        t.start()
    for t in observers:
        t.join(30)
    stop.set()
    for t in readers:
        t.join(10)
    assert not errors, errors[:3]
    exp = h.export()
    assert exp["count"] == n_threads * per_thread
    assert sum(exp["counts"]) == exp["count"]
    assert exp["sum"] == pytest.approx(
        n_threads * sum(i % 10 for i in range(per_thread)))


def test_counters_concurrent_add():
    c = Counters()
    threads = [threading.Thread(
        target=lambda: [c.add("x") for _ in range(5000)])
        for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert c.get("x") == 30000


def test_histogram_export_exposes_bucket_bounds():
    """The exposition carries the BOUNDS and per-bucket counts — not
    just the quantile summary — and they round-trip through the prom
    renderer's cumulative le series."""
    h = Histogram((1, 5, 10))
    for v in (0.5, 0.7, 3, 7, 20, 30):
        h.observe(v)
    exp = h.export()
    assert exp["bounds"] == [1, 5, 10]
    assert exp["counts"] == [2, 1, 1, 2]     # last = overflow
    assert exp["count"] == 6 and exp["max"] == 30
    # cumulative rendering ends at the exact count
    w = prom_mod._Writer()
    w.histogram("crdt_x_ms", "t", exp["bounds"], exp["counts"],
                exp["count"], exp["sum"], {"doc": "d"})
    fams = prom_mod.parse_text(w.render())
    buckets = [(lbl["le"], v) for name, lbl, v in
               fams["crdt_x_ms"]["samples"] if name.endswith("_bucket")]
    assert buckets == [("1", 2.0), ("5", 3.0), ("10", 4.0),
                       ("+Inf", 6.0)]


def test_prom_label_values_round_trip_through_escaping():
    """Label values with backslashes, quotes, and newlines must come
    back from parse_text exactly as they went into the writer — a
    label-keyed consumer joining parsed labels to doc ids must not see
    the escaped text."""
    w = prom_mod._Writer()
    for raw in ('a"b', "a\\b", "a\nb", 'tricky\\"mix\n'):
        w = prom_mod._Writer()
        w.counter("crdt_t_total", "t", 1, {"doc": raw})
        fams = prom_mod.parse_text(w.render())
        (_, lbl, v), = fams["crdt_t_total"]["samples"]
        assert lbl["doc"] == raw, (raw, lbl["doc"])
        assert v == 1.0


def test_prom_parser_rejects_inconsistent_exposition():
    with pytest.raises(prom_mod.PromParseError):
        prom_mod.parse_text("# HELP crdt_a b\n# TYPE crdt_a counter\n"
                            "crdt_a 1\n")        # counter sans _total
    with pytest.raises(prom_mod.PromParseError):
        prom_mod.parse_text(
            "# HELP crdt_h t\n# TYPE crdt_h histogram\n"
            'crdt_h_bucket{le="1"} 5\ncrdt_h_bucket{le="+Inf"} 3\n'
            "crdt_h_sum 1\ncrdt_h_count 3\n")     # not cumulative
    with pytest.raises(prom_mod.PromParseError):
        prom_mod.parse_text("# HELP other_x t\n# TYPE other_x gauge\n"
                            "other_x 1\n")        # outside namespace
    with pytest.raises(prom_mod.PromParseError):
        prom_mod.parse_text("# HELP crdt_h t\n# TYPE crdt_h histogram\n"
                            'crdt_h_bucket{le="1"} 1\n'
                            "crdt_h_sum 1\ncrdt_h_count 1\n")  # no +Inf


# -- flight recorder core (satellite) --------------------------------------


def test_flight_ring_wraparound(tmp_path):
    rec = mk_recorder(tmp_path, capacity=8)
    for i in range(20):
        rec.record(base_rec(num_ops=i))
    records = rec.records()
    assert len(records) == 8                       # bounded
    assert [r.num_ops for r in records] == list(range(12, 20))
    assert records[-1].seq == 20                   # seq keeps counting
    st = rec.stats()
    assert st["records_total"] == 20 and st["records"] == 8
    # a manual dump after wraparound carries exactly the ring
    path = rec.dump()
    lines = [json.loads(ln) for ln in
             open(path).read().splitlines()]
    assert lines[0]["flight_dump"] and lines[0]["records"] == 8
    assert [ln["num_ops"] for ln in lines[1:]] == list(range(12, 20))


def test_flight_dump_triggers_and_rate_limit(tmp_path):
    rec = mk_recorder(tmp_path, slo_ms=100.0, min_dump_interval_s=60.0)
    assert rec.record(base_rec()) is None          # under SLO: no dump
    p1 = rec.record(base_rec(total_ms=250.0))      # breach → dump
    assert p1 and os.path.exists(p1) and p1.endswith("_slo.jsonl")
    # second breach inside the rate-limit window is suppressed
    assert rec.record(base_rec(total_ms=300.0)) is None
    st = rec.stats()
    assert st["slo_breaches"] == 2
    assert st["dumps"] == {"slo": 1, "suppressed": 1}
    # audit failure and error outcomes are independent triggers
    rec2 = mk_recorder(tmp_path)
    pa = rec2.record(base_rec(audit={"ok": False, "fast_path": 99}))
    pe = rec2.record(base_rec(outcome="error", error="boom"))
    assert pa.endswith("_audit.jsonl") and pe.endswith("_error.jsonl")
    # a sample_error without a verdict is NOT an audit failure
    assert rec2.record(base_rec(audit={"sample_error": "x"})) is None
    st2 = rec2.stats()
    assert st2["audit_failures"] == 1 and st2["errors"] == 1


# -- the acceptance soak ---------------------------------------------------


def test_soak_every_commit_record_carries_admission_trace_ids(tmp_path):
    """Concurrent pushes across documents: every flight record carries
    ≥1 trace_id, and the records' union covers every id minted at
    admission — a coalesced batch is attributable to ALL its
    requests."""
    rec = mk_recorder(tmp_path, capacity=4096)
    engine = ServingEngine(flight=rec)
    n_docs, writers_per_doc, deltas = 3, 3, 4
    submitted_ids = set()
    ids_lock = threading.Lock()
    errors = []

    def writer(doc_id, rid, widx):
        counter, anchor = 0, 0
        for d in range(deltas):
            ops = chain_ops(rid, 8, counter0=counter, anchor=anchor)
            counter += 8
            anchor = rid * OFFSET + counter
            tid = f"soak-{doc_id}-w{widx}-{d:02d}"
            with ids_lock:
                submitted_ids.add(tid)
            try:
                acc, _ = engine.submit(doc_id, json_codec.dumps(
                    Batch(tuple(ops))), trace_id=tid)
                if not acc:
                    errors.append(f"{tid} rejected")
            except Exception as e:      # noqa: BLE001 — test capture
                errors.append(f"{tid}: {e!r}")

    threads = [threading.Thread(target=writer,
                                args=(f"doc{i}", 1 + w, w), daemon=True)
               for i in range(n_docs) for w in range(writers_per_doc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    # the flush barrier replaces close()-as-barrier: records are
    # guaranteed recorded, and the engine KEEPS serving afterwards.
    # close() before asserting — a failure must not leak a live
    # scheduler thread into the rest of the test session
    try:
        flushed = engine.flush(timeout=60)
    finally:
        engine.close()
    assert flushed
    assert not errors, errors[:5]

    records = rec.records()
    assert records, "no commit records"
    seen_ids = set()
    for r in records:
        assert len(r.trace_ids) >= 1, f"record {r.seq} has no trace_id"
        assert r.outcome in ("committed", "partial", "noop")
        assert r.coalesce_width >= 1
        assert set(r.stages_ms) >= {"parse", "fuse"}
        assert r.fingerprint and r.snapshot_seq >= 1
        seen_ids.update(r.trace_ids)
    assert seen_ids == submitted_ids, \
        f"missing: {sorted(submitted_ids - seen_ids)[:5]}"
    # coalescing happened at least once under 3 concurrent writers, or
    # every commit was width-1 — either way the widths sum to the
    # request count
    assert sum(r.coalesce_width for r in records) == len(submitted_ids)


def test_slo_breach_dumps_full_stage_breakdown(tmp_path):
    """A deliberately slow commit (over the recorder's SLO) triggers a
    JSONL dump whose record carries the full parse/fuse/merge/publish
    breakdown and the admission trace id."""
    rec = mk_recorder(tmp_path, slo_ms=120.0)
    engine = ServingEngine(flight=rec)
    try:
        engine.submit("slo", json_codec.dumps(
            Batch(tuple(chain_ops(1, 8)))), trace_id="slo-fast-commit")
        doc = engine.get("slo")
        real = doc.tree.apply_packed

        def slow(*a, **k):
            time.sleep(0.3)
            return real(*a, **k)

        doc.tree.apply_packed = slow
        engine.submit("slo", json_codec.dumps(
            Batch(tuple(chain_ops(1, 8, counter0=8,
                                  anchor=OFFSET + 8)))),
            trace_id="slo-slow-commit")
    finally:
        engine.close()
    st = rec.stats()
    assert st["slo_breaches"] == 1
    path = st["last_dump_path"]
    assert path and path.endswith("_slo.jsonl")
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert lines[0]["reason"] == "slo"
    slow_recs = [ln for ln in lines[1:]
                 if "slo-slow-commit" in ln.get("trace_ids", ())]
    assert len(slow_recs) == 1
    r = slow_recs[0]
    assert r["total_ms"] > 120.0
    assert set(r["stages_ms"]) >= {"parse", "fuse", "merge", "publish"}
    assert r["stages_ms"]["merge"] > 250.0       # the injected sleep
    assert r["outcome"] == "committed" and r["fingerprint"]


def test_audit_failure_is_a_dump_trigger_through_the_engine(tmp_path):
    """The sampled chain audit as a production tripwire: a batch whose
    trace exceeds the CI budget produces an audit record with
    ``ok: false`` and a JSONL dump.  (Sampling a small batch with
    ``audit_min_ops=0`` IS the genuine failure mode — the compacted
    tiers dominate a tiny threshold, exactly what the min-width gate
    exists to exclude in production.)"""
    rec = mk_recorder(tmp_path, audit_every=1, audit_min_ops=0)
    engine = ServingEngine(flight=rec)
    try:
        engine.submit("au", json_codec.dumps(
            Batch(tuple(chain_ops(1, 40)))), trace_id="audit-fail-trace")
    finally:
        engine.close()
    st = rec.stats()
    assert st["audit_failures"] == 1
    records = rec.records()
    audited = [r for r in records if r.audit is not None]
    assert len(audited) == 1
    a = audited[0].audit
    assert a["ok"] is False and a["fast_path"] > a["budget"]
    path = st["last_dump_path"]
    assert path and path.endswith("_audit.jsonl")
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert lines[0]["reason"] == "audit"
    assert any("audit-fail-trace" in ln.get("trace_ids", ())
               for ln in lines[1:])


def test_engine_exception_records_error_and_dumps(tmp_path):
    """An engine exception resolves the handler with 500 AND leaves an
    error record + dump behind (the crash-post-mortem path)."""
    rec = mk_recorder(tmp_path)
    engine = ServingEngine(flight=rec)
    try:
        engine.submit("err", json_codec.dumps(
            Batch(tuple(chain_ops(1, 5)))))
        doc = engine.get("err")

        def boom(*a, **k):
            raise RuntimeError("injected launch failure")

        doc.tree.apply_packed = boom
        with pytest.raises(SchedulerError):
            engine.submit("err", json_codec.dumps(
                Batch(tuple(chain_ops(1, 5, counter0=5,
                                      anchor=OFFSET + 5)))),
                trace_id="err-trace-0001")
    finally:
        engine.close()
    st = rec.stats()
    assert st["errors"] == 1 and st["dumps"].get("error") == 1
    err_recs = [r for r in rec.records() if r.outcome == "error"]
    assert len(err_recs) == 1
    assert err_recs[0].trace_ids == ("err-trace-0001",)
    assert "injected launch failure" in err_recs[0].error


def test_flight_record_staleness_and_queue_depth(tmp_path):
    """Snapshot staleness at publish and admission queue depth land on
    the record: a staged multi-delta round (scheduler paused) reports
    the depth its members saw."""
    rec = mk_recorder(tmp_path)
    engine = ServingEngine(flight=rec)
    try:
        engine.submit("sq", json_codec.dumps(
            Batch(tuple(chain_ops(1, 4)))))
        time.sleep(0.15)    # age the published snapshot measurably
        engine.scheduler.pause()
        boxes = []
        for k in range(3):
            body = json_codec.dumps(Batch(tuple(
                chain_ops(2 + k, 4))))
            th = threading.Thread(
                target=lambda b=body: engine.submit("sq", b),
                daemon=True)
            th.start()
            boxes.append(th)
            deadline = time.monotonic() + 10
            while len(engine.get("sq").queue) < k + 1:
                assert time.monotonic() < deadline
                time.sleep(0.002)
        engine.scheduler.resume()
        for th in boxes:
            th.join(30)
    finally:
        engine.close()
    records = rec.records()
    assert len(records) == 2
    fused = records[-1]
    assert fused.coalesce_width == 3
    assert fused.queue_depth_admission == 2     # deepest member saw 2
    assert fused.staleness_s >= 0.14            # the aged snapshot
    assert records[0].staleness_s < 10          # sanity: both stamped


# -- the exposition surface over HTTP --------------------------------------


def test_http_prom_and_flight_endpoints(server, req):
    """/metrics/prom parses under the strict naming contract; /debug/
    flight carries the commit records; POST echoes X-Trace-Id."""
    import http.client
    port = server.server_port
    body = json_codec.dumps(Batch(tuple(chain_ops(1, 12))))
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/docs/obs/ops", body=body,
                 headers={"X-Trace-Id": "client-chose-this-id"})
    resp = conn.getresponse()
    payload = json.loads(resp.read())
    assert resp.status == 200
    assert resp.getheader("X-Trace-Id") == "client-chose-this-id"
    assert payload["trace_id"] == "client-chose-this-id"

    # malformed client id: re-minted, echoed
    conn.request("POST", "/docs/obs/ops", body=json_codec.dumps(
        Batch(tuple(chain_ops(1, 6, counter0=12, anchor=OFFSET + 12)))),
        headers={"X-Trace-Id": "bad id!"})
    resp = conn.getresponse()
    payload2 = json.loads(resp.read())
    minted = resp.getheader("X-Trace-Id")
    assert minted != "bad id!" and payload2["trace_id"] == minted

    # unified prom exposition parses strictly
    conn.request("GET", "/metrics/prom")
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/plain")
    text = resp.read().decode()
    conn.close()
    fams = prom_mod.parse_text(text)
    for family in ("crdt_doc_ops_merged_total",
                   "crdt_doc_commit_latency_ms",
                   "crdt_doc_coalesce_width", "crdt_span_ms_total",
                   "crdt_flight_records_total"):
        assert family in fams, family
    assert fams["crdt_doc_commit_latency_ms"]["type"] == "histogram"
    merged = [v for n, lbl, v in
              fams["crdt_doc_ops_merged_total"]["samples"]
              if lbl.get("doc") == "obs"]
    assert merged == [18.0]
    spans = {lbl["span"] for _, lbl, _ in
             fams["crdt_span_ms_total"]["samples"]}
    assert {"serve.parse", "serve.merge", "serve.publish"} <= spans

    # flight debug endpoint: both commits, trace ids attached.  Records
    # land asynchronously after the POST returns — the flush barrier
    # (not a records_total poll) makes the one-shot scrape safe.
    assert server.store.flush(timeout=30)
    st, flight = req(server, "GET", "/debug/flight")
    assert st == 200
    recs = flight["records"]
    assert len(recs) == 2
    assert recs[0]["trace_ids"] == ["client-chose-this-id"]
    assert recs[1]["trace_ids"] == [minted]
    for r in recs:
        assert set(r["stages_ms"]) >= {"parse", "merge", "publish"}
        assert r["fingerprint"]


def test_autouse_fixture_resets_spans_and_default_recorder():
    """Span registry and default flight recorder start empty for every
    test (the autouse conftest fixture) — span assertions no longer
    depend on which serving test ran first."""
    from crdt_graph_tpu.utils import profiling
    assert profiling.span_stats("serve.") == {}
    assert flight_mod.get_default_recorder().stats()["records_total"] \
        == 0
