"""Program spans on the profiler's clock: ``utils.profiling.span`` is a
``jax.profiler.TraceAnnotation`` as well as a registry entry, the
scheduler times each ticket's queue wait, and the engine's staged apply
times its prepare, launch, device wait and finish."""
import glob
import threading
import time

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.codec import json_codec                  # noqa: E402
from crdt_graph_tpu.core.operation import Add, Batch         # noqa: E402
from crdt_graph_tpu.obs import flight as flight_mod          # noqa: E402
from crdt_graph_tpu.serve import ServingEngine               # noqa: E402
from crdt_graph_tpu.utils import profiling                   # noqa: E402

OFFSET = 2**32
ENGINE_SPANS = ("engine.prepare", "engine.launch", "engine.sync",
                "engine.finish")


def chain_body(rid, n):
    ops, prev = [], 0
    for i in range(n):
        ts = rid * OFFSET + i + 1
        ops.append(Add(ts, (prev,), i & 0xFF))
        prev = ts
    return json_codec.dumps(Batch(tuple(ops)))


def recorder(tmp_path):
    return flight_mod.FlightRecorder(capacity=64, slo_ms=60_000.0,
                                     audit_every=0,
                                     dump_dir=str(tmp_path))


def host_events(log_dir):
    """``{line index: [(name, start_ns, end_ns, stats)]}`` of the
    ``serve.*``/``engine.*`` events on the host plane of a capture."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                   for ev in line.events
                   if ev.name.startswith(("serve.", "engine."))]
            if evs:
                out[i] = evs
    return out


def test_spans_are_trace_events_with_args_and_nesting(tmp_path):
    """Spans on two threads under a profiler capture: one host line per
    thread, each event named as its span, its args as stats, the
    inner span inside the outer one; the registry counts them too."""
    def work(tag):
        with profiling.span("serve.round", docs=2, tag=tag):
            time.sleep(0.005)
            with profiling.span("engine.prepare", rows=131072, chunk=3):
                time.sleep(0.005)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        th = threading.Thread(target=work, args=("worker",))
        th.start()
        work("main")
        th.join(30)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()
    lines = host_events(tmp_path)
    assert len(lines) == 2
    tags = set()
    for evs in lines.values():
        (outer,) = [e for e in evs if e[0] == "serve.round"]
        (inner,) = [e for e in evs if e[0] == "engine.prepare"]
        assert outer[3]["docs"] == 2
        tags.add(outer[3]["tag"])
        assert inner[3] == {"rows": 131072, "chunk": 3}
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert inner[2] - inner[1] >= 4_000_000
    assert tags == {"main", "worker"}
    stats = profiling.span_stats()
    assert stats["serve.round"]["count"] == 2
    assert stats["engine.prepare"]["count"] == 2


def test_record_adds_to_the_registry_alone():
    profiling.record("serve.queue_wait", 30.0)
    profiling.record("serve.queue_wait", 10.0)
    s = profiling.span_stats("serve.queue_wait")["serve.queue_wait"]
    assert (s["count"], s["total_ms"], s["max_ms"]) == (2, 40.0, 30.0)
    assert s["mean_ms"] == 20.0


def test_queue_wait_of_a_held_delta(tmp_path):
    """A delta held 200 ms behind a stopped scheduler reads at least
    200 ms of queue wait in the registry and in its flight record."""
    rec = recorder(tmp_path)
    engine = ServingEngine(start=False, flight=rec)
    box = {}
    th = threading.Thread(
        target=lambda: box.update(r=engine.submit("qw", chain_body(1, 3))))
    try:
        th.start()
        deadline = time.monotonic() + 10
        while len(engine.get("qw").queue) < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        time.sleep(0.2)
        assert engine.scheduler.step() == 1
        th.join(30)
    finally:
        engine.close()
    assert box["r"][0] is True
    s = profiling.span_stats()
    assert s["serve.queue_wait"]["count"] == 1
    assert s["serve.queue_wait"]["max_ms"] >= 200.0
    assert s["serve.round"]["count"] == 1
    (r,) = rec.records()
    assert r.queue_wait_ms >= 200.0
    assert r.to_json()["queue_wait_ms"] == r.queue_wait_ms


def test_chunked_apply_spans_each_chunk_inside_the_merge_stage(tmp_path):
    """A 3,072-op push merges in one launch: one each of the engine's
    prepare, launch, sync and finish spans, the spill inside the
    finish, and together they take no longer than the commit's merge
    stage."""
    rec = recorder(tmp_path)
    engine = ServingEngine(flight=rec)
    try:
        engine.submit("ch", chain_body(2, 3072))
        assert engine.flush(60)
    finally:
        engine.close()
    (r,) = rec.records()
    assert r.chunk_count == 1
    s = profiling.span_stats()
    for name in ENGINE_SPANS + ("engine.spill",):
        assert s[name]["count"] == 1, name
    assert s["engine.spill"]["total_ms"] <= s["engine.finish"]["total_ms"]
    inside = sum(s[name]["total_ms"] for name in ENGINE_SPANS)
    assert 0 < inside <= r.stages_ms["merge"]


def test_http_read_and_receive_spans(server, req):
    """The front end times each POST body read and each document or
    log read."""
    status, _ = req(server, "POST", "/docs/h/ops", chain_body(3, 4))
    assert status == 200
    assert req(server, "GET", "/docs/h")[0] == 200
    assert req(server, "GET", "/docs/h/ops?since=0")[0] == 200
    assert req(server, "GET", "/docs")[0] == 200
    s = profiling.span_stats()
    assert s["serve.recv"]["count"] == 1
    assert s["serve.read"]["count"] == 2
    assert s["serve.parse"]["count"] == 1
