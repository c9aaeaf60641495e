"""End-to-end service benchmark: one client bootstraps a 1M-op document
over real HTTP — POST the full wire batch (native parse + kernel merge),
then GET the full log back (native egress) and GET the binary snapshot.

This is the system number the subsystem benches compose into: HTTP +
fastcodec ingest + merge kernel + fastcodec egress + snapshot encode,
measured wall-clock on the serving path.  CPU-only by default (pins the
platform; the kernel merge itself is the bench.py headline on device).

Prints one JSON line per leg; append to the round sweep artifact.
"""
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from http.client import HTTPConnection  # noqa: E402

from crdt_graph_tpu import native  # noqa: E402
from crdt_graph_tpu.bench import workloads  # noqa: E402
from crdt_graph_tpu.codec import packed  # noqa: E402
from crdt_graph_tpu.service import make_server  # noqa: E402


def main(n: int = 1_000_000) -> None:
    srv = make_server(port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_port

    arrs = workloads.chain_workload(64, n)
    p = packed.PackedOps(
        kind=arrs["kind"], ts=arrs["ts"], parent_ts=arrs["parent_ts"],
        anchor_ts=arrs["anchor_ts"], depth=arrs["depth"],
        paths=arrs["paths"], value_ref=arrs["value_ref"],
        pos=arrs["pos"], values=[f"v{i % 997}" for i in range(n)],
        num_ops=n, parent_pos=arrs["parent_pos"],
        anchor_pos=arrs["anchor_pos"], target_pos=arrs["target_pos"],
        ts_rank=arrs["ts_rank"], hints_vouched=True)
    wire = native.encode_pack(p)

    def req(method, path, body=None, read=True):
        conn = HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        data = resp.read() if read else b""
        conn.close()
        return resp.status, data

    legs = []
    t0 = time.perf_counter()
    st, out = req("POST", "/docs/e2e/ops", wire)
    t1 = time.perf_counter()
    assert st == 200 and json.loads(out)["accepted"], out[:200]
    legs.append({"metric": "service_e2e_1M", "leg": "post_ops",
                 "seconds": round(t1 - t0, 3), "bytes": len(wire),
                 "note": "HTTP + native parse + kernel merge + "
                         "status encode"})

    # warm repeat onto a FRESH document: jit caches hot, so this is the
    # steady-state serving cost (the r4 "warm" row) — the one VERDICT
    # r4 next-5 targets (≤2 s at 1M)
    t0 = time.perf_counter()
    st, out = req("POST", "/docs/e2e_warm/ops", wire)
    t1 = time.perf_counter()
    assert st == 200 and json.loads(out)["accepted"], out[:200]
    legs.append({"metric": "service_e2e_1M", "leg": "post_ops_warm",
                 "seconds": round(t1 - t0, 3), "bytes": len(wire)})

    t0 = time.perf_counter()
    st, log_bytes = req("GET", "/docs/e2e/ops?since=0")
    t1 = time.perf_counter()
    assert st == 200
    legs.append({"metric": "service_e2e_1M", "leg": "get_ops_bootstrap",
                 "seconds": round(t1 - t0, 3), "bytes": len(log_bytes)})

    t0 = time.perf_counter()
    st, snap = req("GET", "/docs/e2e/snapshot")
    t1 = time.perf_counter()
    assert st == 200
    legs.append({"metric": "service_e2e_1M", "leg": "get_snapshot",
                 "seconds": round(t1 - t0, 3), "bytes": len(snap)})

    for leg in legs:
        print(json.dumps(leg), flush=True)
    srv.shutdown()


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000)
