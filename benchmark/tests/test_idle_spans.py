"""Device idle time put down to the program span that was open."""
import glob
import os
import threading
import time

import pytest

from benchmark import idle_spans as isp

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def test_scheduler_spans_first_then_other_threads_split_at_edges():
    sched = [(0, 100 * MS, "serve.round"), (10 * MS, 90 * MS, "serve.merge"),
             (20 * MS, 40 * MS, "engine.prepare"),
             (50 * MS, 70 * MS, "engine.sync")]
    handler = [(0, 200 * MS, "serve.recv"), (120 * MS, 130 * MS,
                                              "serve.parse")]
    gaps = [(15 * MS, 25 * MS), (60 * MS, 75 * MS), (95 * MS, 140 * MS),
            (250 * MS, 260 * MS), (300 * MS, 300 * MS + 500_000)]
    out = isp.label_idle(gaps, [handler, sched])
    assert out == {
        "serve.merge": 5 * MS + 5 * MS,
        "engine.prepare": 5 * MS,
        "engine.sync": 10 * MS,
        "serve.round": 5 * MS,
        "other:serve.recv": 20 * MS + 10 * MS,
        "other:serve.parse": 10 * MS,
        isp.NO_SPAN: 10 * MS,
        isp.SHORT_LABEL: 500_000,
    }


def test_gaps_of_two_devices_count_twice():
    sched = [(0, 10 * MS, "serve.round")]
    out = isp.label_idle([(0, 4 * MS), (2 * MS, 6 * MS)], [sched])
    assert out == {"serve.round": 8 * MS}


def test_without_a_scheduler_line_every_span_is_other():
    out = isp.label_idle([(0, 10 * MS)], [[(0, 5 * MS, "engine.mirror")]])
    assert out == {"other:engine.mirror": 5 * MS, isp.NO_SPAN: 5 * MS}


def test_a_cpu_capture_of_the_program_spans(tmp_path):
    """Spans from the program's own ``profiling.span`` on two threads,
    captured on the CPU, read back per thread line and labelled."""
    import jax

    from crdt_graph_tpu.utils import profiling

    def handler():
        with profiling.span("serve.recv", bytes=10):
            time.sleep(0.03)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with profiling.span("serve.round", docs=1, tickets=1,
                            queue_wait_ms_max=0.0):
            th = threading.Thread(target=handler)
            th.start()
            with profiling.span("engine.prepare", rows=8, chunk=0):
                time.sleep(0.01)
            th.join(30)
        time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    lines = isp.read_spans(path)
    names = sorted(sorted(n for _, _, n in evs) for evs in lines)
    assert names == [["engine.prepare", "serve.round"], ["serve.recv"]]
    sched, = [evs for evs in lines if len(evs) == 2]
    rnd, = [e for e in sched if e[2] == "serve.round"]
    prep, = [e for e in sched if e[2] == "engine.prepare"]
    assert rnd[0] <= prep[0] and prep[1] <= rnd[1]
    # the whole round idle, and 10 ms past it
    out = isp.label_idle([(rnd[0], rnd[1] + 10 * MS)], lines)
    assert out["engine.prepare"] == prep[1] - prep[0]
    assert out["serve.round"] == rnd[1] - rnd[0] - (prep[1] - prep[0])
    assert sum(out.values()) == rnd[1] + 10 * MS - rnd[0]
    assert set(out) <= {"engine.prepare", "serve.round",
                        "other:serve.recv", isp.NO_SPAN}


def test_recorded_trace_without_program_spans():
    """The recorded v5e trace predates the spans: all its idle time has
    no span open, and the share is not reported."""
    got = isp.reduce_file(os.path.join(DATA, "merge_v5e.xplane.pb.gz"))
    assert got["idle_unspanned_share"] is None
    labels = dict(got["idle_spans"])
    assert set(labels) <= {isp.NO_SPAN, isp.SHORT_LABEL}
    assert labels[isp.NO_SPAN] > 0


@pytest.mark.parametrize("unspanned_ms,share", [(0, 0.0), (50, 100 / 3)])
def test_unspanned_share(monkeypatch, unspanned_ms, share):
    """The share is the ``no span open`` part of all device-idle time,
    short gaps included."""
    lines = [[(0, 100 * MS, "serve.round")]]
    gaps = [(0, 99 * MS + 500_000), (99 * MS + 500_000, 100 * MS),
            (100 * MS, (100 + unspanned_ms) * MS)]
    monkeypatch.setattr(isp.trace_reduce, "reduce_file",
                        lambda path: {"gaps": gaps, "devices": 1})
    monkeypatch.setattr(isp, "read_spans", lambda path: lines)
    got = isp.reduce_file("unused")
    assert got["idle_unspanned_share"] == pytest.approx(share)
