"""Tier-1 wiring for scripts/serve_smoke.py: the serving engine's
end-to-end gate (concurrent pushes + reads across documents over real
HTTP, convergence, clean shutdown) runs fast and unmarked so every
tier-1 pass exercises the scheduler."""
import importlib.util
import json
import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

_spec = importlib.util.spec_from_file_location(
    "_serve_smoke",
    os.path.join(os.path.dirname(__file__), "..", "scripts",
                 "serve_smoke.py"))
_serve_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_serve_smoke)


def test_fleet_smoke_end_to_end():
    """--fleet mode (ISSUE 7): 3 servers, one write per server, RYW
    through a DIFFERENT server after anti-entropy, fingerprint-equal
    reads everywhere, cluster scrape surface on every member."""
    summary = _serve_smoke.run_fleet(n_servers=3, n_docs=2)
    assert summary["writes"] == 6
    assert summary["cross_server_ryw"] == 6
    assert summary["forwarded"] > 0
    assert summary["fleet0"]["visible"] == 15      # 3 servers x 5 adds


def test_fleet_procs_shm_exact_ledger():
    """--fleet-procs mode (ISSUE 17): 3 REAL processes converge on one
    document over 4 generations of the host-shared body cache — per
    generation exactly one encode on the whole host (misses +1) and an
    attach from everyone else (hits +(N-1)), zero degraded attaches,
    zero leaked segments.  The assertions live in run_fleet_procs; the
    summary re-pins the ledger at the tier-1 surface."""
    summary = _serve_smoke.run_fleet_procs(n_procs=3, gens=4)
    assert summary["misses"] == 4
    assert summary["hits"] == 8
    assert summary["shared_bytes"] > 0


def test_mergetier_smoke_end_to_end():
    """--mergetier mode (disaggregated merge tier): 3 docs through a
    REAL worker server over HTTP coalesce into ONE width-3 launch,
    zero fallbacks, bit-identical to a local-only control engine, and
    both scrape surfaces (front-end + worker) strict-parse."""
    summary = _serve_smoke.run_mergetier(n_docs=3, n_ops=1200)
    assert summary["remote_docs"] == 3
    assert summary["batch_width_max"] == 3
    assert summary["launches"] == 1
    assert summary["fallbacks"] == {}


def test_serve_smoke_end_to_end():
    summary = _serve_smoke.run(n_docs=4, writers_per_doc=3, deltas=3,
                               delta_size=8)
    assert len([k for k in summary if k.startswith("smoke")]) == 4
    assert summary["scheduler"]["queue_depth_total"] == 0
    # the telemetry exposition is pinned in tier-1: the smoke scraped
    # /metrics/prom (strict parse) and /debug/flight (trace-id
    # coverage) before shutting down
    assert summary["flight"]["records_total"] >= 1
    assert summary["flight"]["trace_ids_seen"] >= 36   # 4×3×3 pushes
    # pooled keep-alive clients (ISSUE 15): connections were REUSED
    # (the smoke asserts reuses > opens internally too) and the old
    # TIME_WAIT transport flake is gone by construction — a clean run
    # fires zero genuine retries
    assert summary["connpool"]["reuses"] > summary["connpool"]["opens"]
    assert summary["transport_retries"] == 0


def test_kernel_post_publishes_from_table_until_first_small_delta():
    """A kernel-size POST publishes a snapshot read straight from the
    merged table: the served values equal the reference and no host
    mirror is built; the first small delta to the same document builds
    it, once, and its ack is read back."""
    import threading
    import urllib.request

    import crdt_graph_tpu as crdt
    from crdt_graph_tpu.codec import json_codec
    from crdt_graph_tpu.service import make_server

    srv = make_server(port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_port}/docs/k"
    counters = srv.store.counters

    def post(ops):
        body = json_codec.dumps(crdt.Batch(tuple(ops))).encode()
        req = urllib.request.Request(url + "/ops", data=body,
                                     method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200

    def values():
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.loads(r.read())["values"]

    def chain(rid, n, start=0):
        ops, prev = [], start
        for c in range(1, n + 1):
            ts = rid * 2**32 + c
            ops.append(crdt.Add(ts, (prev,), f"r{rid}.{c}"))
            prev = ts
        return ops

    try:
        bulk = chain(3, 700) + chain(5, 700)
        ref = crdt.init(1).apply(crdt.Batch(tuple(bulk)))
        c0 = counters.snapshot()
        post(bulk)
        assert values() == ref.visible_values()
        c1 = counters.snapshot()
        tree = srv.store.get("k").tree
        assert tree._mirror is None
        assert c1["visible_from_table"] - c0["visible_from_table"] >= 1
        assert c1["mirror_builds"] == c0["mirror_builds"]
        delta = chain(7, 3)
        post(delta)
        ref = ref.apply(crdt.Batch(tuple(delta)))
        assert values() == ref.visible_values()
        assert tree._mirror is not None
        assert counters.get("mirror_builds") - c1["mirror_builds"] == 1
    finally:
        srv.shutdown()
        srv.server_close()
